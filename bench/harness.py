"""The benchmark harness: one cell, one seed, one window.

A cell names a configuration (``bench/configs/<config>.json``, whose
``generator`` names ``bench/graphs/<generator>.py``) and a traffic mix
(``bench/traffic/<mix>.json``); every metric, end-to-end and per-layer,
is a reader of its own in ``bench/metrics/<metric>.py``.  All
are found by the names in ``BENCHMARK.json``, so a new cell or metric is a
new file, not an edit here.

The system under test is the program's serving path, wired as its own
entry points wire it: ``GraphRegistry.register`` then
``MicroBatchScheduler.submit`` / ``tick`` under the default
``DispatchPolicy``.  The run's seed draws the graph (``bench/graphs``) and
the jobs (``bench/traffic.py``).  Set-up warms the cell's shapes with one
job of its own; the window then drives the jobs for ``--seconds`` and
finishes the job in flight; afterwards the answers of the window are
compared bit for bit with the plain reference (``bench/reference.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from bench import graphs, reference, traffic

# a new executable, compiled or loaded from the persistent cache
COMPILE_EVENTS = {"/jax/core/compile/backend_compile_duration": "compiled",
                  "/jax/compilation_cache/cache_retrieval_time_sec":
                      "loaded"}
VIA = {"p2p": "target", "rows": "batch"}
POOL_MIN_WORK = 5_000_000   # reference arcs x rows above which workers help


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    mix = traffic.load(w["traffic"], root=os.path.join(root, "bench"))

    def mine(m):
        return name in m["workloads"] if "workloads" in m else None

    e2e = [m for m in spec["end_to_end"] if mine(m) in (True, None)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if mine(m) or (mine(m) is None and m["moves"] in names)]
    return Cell(name, w["chips"], config, mix, e2e, per_layer)


def reader(metric: str, root: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the window -------------------------------------------------------------

@dataclasses.dataclass
class Sent:
    """One job as sent: its answers and its times."""
    job: traffic.Job
    sent: float
    done: float = 0.0
    answers: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Window:
    start: float
    sent: list
    late_ms: list           # from the last answer to the next send
    passes: int             # times the window went through the job list

    @property
    def last(self) -> float:
        return max(s.done for s in self.sent)


class Profile:
    """The profiler and the program's span tracer, on for the first
    ``jobs`` jobs of the window."""

    def __init__(self, jax, jobs: int):
        self.jax, self.left = jax, jobs
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.tracer = None
        self.active = False

    def start(self) -> None:
        from repro.obs.trace import Tracer, set_tracer

        self.tracer = Tracer()
        self._prev = set_tracer(self.tracer)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # keep host overhead low
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = self.jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.active = True

    def job_done(self) -> None:
        self.left -= 1
        if self.active and self.left <= 0:
            self.stop()

    def stop(self) -> None:
        from repro.obs.trace import set_tracer

        if self.active:
            self._ann.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
            set_tracer(self._prev)
            self.active = False

    def xplane(self) -> str:
        for dirpath, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(dirpath, f)
        raise FileNotFoundError(f"no .xplane.pb under {self.dir}")

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def drive(jax, sched, names: list, jobs: list, *, seconds: float,
          profile: Profile | None = None) -> Window:
    """One closed-loop client: send a job, tick until all its answers are
    in, send the next, for ``seconds`` (one job at least); the job in
    flight at the close is finished and kept.  The job list starts again
    when it runs out."""
    ann = jax.profiler.TraceAnnotation
    clock = time.perf_counter
    sent, late = [], []
    if profile is not None:
        profile.start()
    start = free = clock()
    while not sent or free < start + seconds:
        job = jobs[len(sent) % len(jobs)]
        with ann("bench.submit"):
            t = clock()
            s = Sent(job, t)
            qids = {sched.submit(names[job.graph], src, tgt).qid
                    for src, tgt in job.queries}
        late.append((t - free) * 1e3)
        sent.append(s)
        while len(s.answers) < len(qids):
            with ann("bench.tick"):
                answers = sched.tick()
            done = clock()
            if not answers:
                raise RuntimeError("a tick answered nothing of a job")
            with ann("bench.answer"):
                for a in answers:
                    if a.query.qid not in qids:
                        raise RuntimeError(f"an answer to {a.query.qid}, "
                                           "which this job did not ask")
                    s.answers.append(a)
        s.done = free = done
        if profile is not None:
            profile.job_done()
    if profile is not None:
        profile.stop()
    return Window(start, sent, late, passes=-(-len(sent) // len(jobs)))


# -- the reference comparison -------------------------------------------------

CHECK_STREAM = 2        # a run's seed picks the compared sources from here


def _pool_map(fn, args: list, config: dict, seed: int, count: int,
              precision: str, root: str):
    import multiprocessing

    workers = max(1, min(len(args), (os.cpu_count() or 2) - 1))
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(workers, mp_context=ctx,
                             initializer=reference.init_worker,
                             initargs=(config, seed, count, precision,
                                       root)) as ex:
        return list(ex.map(fn, args))


def reference_rows(csrs: list, config: dict, seed: int, picked: list,
                   root: str, precision: str = "f32") -> dict:
    """The reference's full row from each picked ``(graph, source)``;
    worker processes rebuild the graphs with the generator of the
    checkout at ``root``."""
    if len(picked) * csrs[0].arcs < POOL_MIN_WORK:
        dij = {g: reference.Dijkstra(csrs[g], precision)
               for g in sorted({g for g, _ in picked})}
        rows = [dij[g].solve(s) for g, s in picked]
    else:
        rows = _pool_map(reference.worker_solve, list(picked), config, seed,
                         len(csrs), precision, root)
    return dict(zip(picked, rows))


def bits(x) -> np.ndarray:
    return np.asarray(x, np.float32).reshape(-1).view(np.uint32)


def same_bits(got, want) -> bool:
    """``got`` is bit for bit ``want``; ``None`` (no answer, or an
    error) never is."""
    return (got is not None and bits(got).shape == bits(want).shape
            and bool(np.all(bits(got) == bits(want))))


def picked_sources(queries: list, seed: int, count: int) -> list:
    """The ``(graph, source)`` pairs whose answers are compared: ``count``
    of the distinct pairs of ``queries``, drawn from the run's seed (all
    where fewer)."""
    pairs = sorted({(g, s) for g, s, _ in queries})
    rng = traffic.rng_for(seed, CHECK_STREAM)
    pick = rng.choice(len(pairs), size=min(count, len(pairs)), replace=False)
    return sorted(pairs[i] for i in pick)


def check(csrs: list, config: dict, seed: int, queries: list, values: list,
          picked: list, root: str) -> tuple:
    """``(wrong, compared)``.  Every answer of a picked source is compared
    with the reference's row from it; an answer of another source counts
    wrong only where it is missing or an error."""
    rows = reference_rows(csrs, config, seed, picked, root)
    wrong = compared = 0
    for (g, s, t), got in zip(queries, values, strict=True):
        row = rows.get((g, s))
        if row is not None:
            compared += 1
            wrong += not same_bits(got, row if t is None else row[t])
        else:
            wrong += got is None
    return wrong, compared


def served_values(window: Window, kind: str) -> tuple:
    """``(queries, values)`` of every answer in the window, a query as
    ``(graph, source, target)``; a value is ``None`` unless the answer is
    ok, exact and from the expected path."""
    queries, values = [], []
    for s in window.sent:
        for a in s.answers:
            queries.append((s.job.graph, a.query.source, a.query.target))
            good = a.ok and a.exact and a.via == VIA[kind]
            values.append(a.value if good else None)
    return queries, values


# -- one run ------------------------------------------------------------------

class Compiles:
    """Counts JAX's compile and cache-load events."""

    def __init__(self, jax):
        self.counts = {"compiled": 0, "loaded": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.counts[COMPILE_EVENTS[event]] += 1

    def since(self, before: dict) -> dict:
        return {k: v - before[k] for k, v in self.counts.items()}


def set_compile_cache(jax, root: str) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``.jax_cache`` at the root of the checkout (a fixed path: the
    path is part of the cache key).  Every program is kept, however
    quickly it compiled."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def run_cell(jax, cell: Cell, seed: int, seconds: float, trace: bool, *,
             t0: float, root: str) -> dict:
    """One run of ``cell``; returns the result line's object."""
    from repro.core.csr import CsrGraph
    from repro.serve import (DispatchPolicy, DistanceCache, GraphRegistry,
                             MicroBatchScheduler)

    compiles = Compiles(jax)
    config, mix = cell.config, cell.mix
    kind = mix["job"]["kind"]
    phases = [("start", time.perf_counter() - t0)]
    gen = graphs.generator(config["generator"], root)
    csrs = [graphs.build(config, seed, g, gen) for g in range(mix["graphs"])]
    phases.append(("graphs", time.perf_counter() - t0))
    warm, jobs = traffic.make_jobs(mix, csrs, seed)
    phases.append(("jobs", time.perf_counter() - t0))
    serving = config["serving"]
    if kind == "rows" and len(csrs) * mix["sources"] <= serving["cache_rows"]:
        raise ValueError("a second pass through the rows jobs would be "
                         "served from the row cache: draw more sources "
                         f"than its {serving['cache_rows']} rows")
    registry = GraphRegistry()
    sched = MicroBatchScheduler(
        registry, DistanceCache(capacity=serving["cache_rows"]),
        max_batch=serving["max_batch"], dispatch=DispatchPolicy())
    names = [f"{config['name']}.{g}" for g in range(len(csrs))]
    for name, csr in zip(names, csrs):
        registry.register(name, CsrGraph(csr.indptr, csr.indices,
                                         csr.weights, csr.n),
                          landmarks=serving["landmarks"])
    phases.append(("register", time.perf_counter() - t0))
    for w in warm:
        drive(jax, sched, names, [w], seconds=0.0)
    setup_s = time.perf_counter() - t0
    phases.append(("warm-up", setup_s))
    in_setup = dict(compiles.counts)
    profile = Profile(jax, mix["trace_jobs"]) if trace else None
    devices = jax.devices()
    try:
        window = drive(jax, sched, names, jobs, seconds=seconds,
                       profile=profile)
        in_window = compiles.since(in_setup)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        spans = profile.tracer.spans if profile is not None else []
        reduced = None
        if profile is not None:
            from bench import trace_reduce

            reduced = trace_reduce.reduce(profile.xplane())
    finally:
        if profile is not None:
            profile.close()
    del sched, registry
    gc.collect()

    queries, values = served_values(window, kind)
    picked = picked_sources(queries, seed, mix["check_sources"])
    wrong, compared = check(csrs, config, seed, queries, values, picked,
                            root)

    ctx = {"cell": cell.name, "kind": kind, "setup_s": setup_s,
           "window": window, "spans": spans, "trace": reduced,
           "graph": {"n": csrs[0].n, "arcs": csrs[0].arcs},
           "device_kind": devices[0].device_kind}
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"], root)(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": wrong == 0, "attempted": len(queries),
              "failed": wrong, "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}

    by_label: dict = {}
    for s in window.sent:
        by_label[s.job.label] = by_label.get(s.job.label, 0) + 1
    log(f"setup_s {setup_s:.3f} (" + ", ".join(
        f"{k} {b - a:.3f}" for (_, a), (k, b) in zip([("", 0.0)] + phases,
                                                      phases))
        + f" s); executables in set-up {in_setup}")
    log(f"jobs answered {len(window.sent)} {dict(sorted(by_label.items()))}"
        f"; queries {len(queries)}; window {window.last - window.start:.3f}"
        f" s; passes through the job list {window.passes}")
    if window.late_ms:
        log(f"client late ms: mean {float(np.mean(window.late_ms)):.3f} "
            f"max {max(window.late_ms):.3f}")
    log(f"executables in the window {in_window}")
    if reduced is not None:
        log(f"traced {reduced['window_s']:.3f} s, device busy "
            f"{reduced['busy_s']:.3f} s; idle by host span "
            f"{reduced['idle_by_host']}")
    checks = {"wrong_answers": {"value": wrong, "limit": 0}}
    result["checks"] = checks
    log(f"compared {compared} answers, those of {len(picked)} sources, "
        "with the reference")
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    return result


def main(workload: str, seed: int, seconds: float, trace: bool, *,
         t0: float, root: str) -> int:
    cell = load_cell(workload, root)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {workload} needs {cell.chips} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    log(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{set_compile_cache(jax, root)}")
    result = run_cell(jax, cell, seed, seconds, trace, t0=t0, root=root)
    print(json.dumps(result), flush=True)
    return 0
