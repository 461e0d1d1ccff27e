"""SSSP serving driver: replay open-loop query traces against the serve
subsystem and report latency/throughput/cache metrics.

    PYTHONPATH=src python -m repro.launch.sssp_serve --smoke

Mirrors launch/serve.py's shape (queue -> batcher -> engine, per-request
latency + aggregate throughput), but for shortest-path queries: per
scenario (uniform / zipf / p2p, see repro/serve/workload.py) the driver
registers the graphs (with ALT landmarks), generates an open-loop arrival
trace, and replays it in wall-clock time — events are submitted when
their arrival time passes, the scheduler ticks whenever work is queued,
and latency = completion - arrival (queueing included, the open-loop
penalty for falling behind).

Reported per scenario: p50/p99/max latency, queries/s, mean batch
occupancy, dedup savings, answers-by-path, cache hit rate.

``--verify`` (default under ``--smoke``) re-solves every distinct
(graph, source) with the ``serial`` engine and asserts each served answer
is bitwise-equal — the end-to-end form of the serving exactness
guarantee (tests/test_serve.py holds the per-component forms).

``--devices P`` runs on a P-device mesh (emulated host devices on the
CPU backend — the MPI-procs analogue — and real chips on a TPU host,
where asking for more than are visible fails) and ``--shard-threshold
N`` routes graphs with >= N vertices through the vertex-partitioned
sharded engines (serve/dispatch.py); ``--verify`` covers the sharded
answers identically, which is how CI's ``--smoke --devices 4`` leg pins
the sharded route to the bitwise guarantee.

``--chaos`` replays a **seeded fault schedule** (serve/faults.py)
through a deterministic closed-loop replay instead of the wall-clock
one: a mixed static + dynamic (churn) trace is submitted in fixed-size
chunks with the event clock as ``tick(now=)``, while the fault plan
fires injected solve/staging failures, mid-tick evictions, poisoned
mutation batches, and sweep clips at the scheduler's seams.  The
verifier then asserts (1) every answer carries a typed status, (2)
every ``exact=True`` answer is bitwise-equal to a fresh ``serial``
solve on the answer-time graph version, (3) degraded p2p answers
bracket the true distance, and (4) every fired fault site surfaced
through its expected status (or the retry counters) — see
README.md §Robustness.  ``--chaos --smoke`` is CI's chaos-smoke entry
point.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core import csr as C
from repro.core.api import shortest_paths
from repro.serve import (STATUS_OK, STATUSES, DispatchPolicy, DistanceCache,
                         GraphRegistry, LatencyRecorder, MicroBatchScheduler,
                         MutationEvent, QueryRejected, SCENARIOS,
                         make_churn_trace, make_trace, set_default_policy)
from repro.launch.runtime import enable_compile_cache, use_devices
from repro.serve.dispatch import DEFAULT_SHARD_THRESHOLD


def replay(sched: MicroBatchScheduler, events) -> list:
    """Wall-clock open-loop replay; returns Answers with done_at stamped.
    A submit rejected by bounded-queue backpressure is dropped (counted
    in the scheduler's ``submissions_rejected``)."""
    events = sorted(events, key=lambda e: e.arrival)
    t0 = time.perf_counter()
    i, answers = 0, []
    while i < len(events) or sched.pending:
        now = time.perf_counter() - t0
        while i < len(events) and events[i].arrival <= now:
            e = events[i]
            try:
                sched.submit(e.graph, e.source, e.target, arrival=e.arrival,
                             deadline=getattr(e, "deadline", None))
            except QueryRejected:
                pass
            i += 1
        if sched.pending:
            out = sched.tick(now)
            done = time.perf_counter() - t0
            for a in out:
                a.done_at = done
            answers.extend(out)
        elif i < len(events):
            time.sleep(min(events[i].arrival - now, 1e-3))
    return answers


def verify_answers(answers, graphs_by_name, *, allow=()) -> int:
    """Assert every ``exact=True`` answer is bitwise-equal to a fresh
    serial solve (degraded p2p answers are instead checked to BRACKET
    the serial distance); returns the number of distinct (graph, source)
    rows checked.  Non-ok statuses listed in ``allow`` are skipped; any
    other failure answer aborts — in a fault-free replay every answer
    must be exact."""
    rows = {}

    def serial_row(graph: str, source: int) -> np.ndarray:
        key = (graph, source)
        if key not in rows:
            rows[key] = shortest_paths(
                graphs_by_name[graph], source, engine="serial").dist
        return rows[key]

    for a in answers:
        q = a.query
        if a.status not in STATUSES:
            raise SystemExit(f"unknown answer status {a.status!r} for {q}")
        if a.status != STATUS_OK:
            if a.status in allow:
                continue
            raise SystemExit(
                f"scheduler returned a {a.status} answer for {q}: "
                f"{a.error}")
        if not a.exact:
            # degraded answers are approximate by contract; a p2p bound
            # pair must still bracket the true distance (admissibility).
            if q.target is not None and a.bounds is not None:
                lb, ub = a.bounds
                want = float(serial_row(q.graph, q.source)[q.target])
                if not (lb <= want * (1 + 1e-4) + 1e-3
                        and want <= ub * (1 + 1e-4) + 1e-3):
                    raise SystemExit(
                        f"degraded bounds ({lb}, {ub}) do not bracket "
                        f"serial {want} for {q}")
            continue
        ref = serial_row(q.graph, q.source)
        if q.target is None:
            if not np.array_equal(a.value, ref):
                raise SystemExit(
                    f"row mismatch vs serial: {q} (via {a.via})")
        else:
            got, want = np.float32(a.value), ref[q.target]
            ok = got == want or (np.isinf(got) and np.isinf(want))
            if not ok:
                raise SystemExit(
                    f"dist mismatch vs serial: {q} (via {a.via}): "
                    f"served {got!r}, serial {want!r}")
    return len(rows)


def run_chaos(args, dispatch) -> None:
    """Seeded chaos replay (see module docstring).  Deterministic closed
    loop: events are submitted in fixed-size chunks with the event clock
    as ``tick(now=)``, so a given (seed, chaos-seed, rates) triple
    replays the exact same fault schedule and answer stream every run."""
    from collections import Counter

    from repro.dynamic import DynamicGraph
    from repro.serve import FaultPlan

    n = args.n or (256 if args.smoke else 2000)
    queries = args.queries or (80 if args.smoke else 400)
    scale = args.fault_rate
    # per-site probe volumes differ by orders of magnitude (solve/clip
    # probe every engine call, mutate only per drained batch), so the
    # multipliers are tuned so every site fires a few times per smoke
    # replay — the reconciliation below is vacuous for a silent site.
    plan = FaultPlan(seed=args.chaos_seed, rates={
        "solve": 0.8 * scale, "stage": 0.4 * scale, "evict": 0.6 * scale,
        "mutate": min(1.0, 4.0 * scale), "clip": 0.5 * scale})

    statics = [(f"g{i}", C.random_csr_graph(n, 3 * n, seed=args.seed + i))
               for i in range(args.graphs)]
    graphs_by_name = dict(statics)
    dyn = DynamicGraph(C.random_csr_graph(n, 3 * n, seed=args.seed + 77))
    registry = GraphRegistry()
    cache = DistanceCache(capacity=args.cache_rows)
    sched = MicroBatchScheduler(
        registry, cache, max_batch=args.batch, dispatch=dispatch,
        faults=plan, retry_budget=2, max_queue=args.max_queue)
    for name, cg in statics:
        registry.register(name, cg, landmarks=args.landmarks,
                          landmark_seed=args.seed)
    registry.register("dyn0", dyn, landmarks=args.landmarks,
                      landmark_seed=args.seed)

    events = make_trace(
        "p2p", [(name, cg.n) for name, cg in statics], num_queries=queries,
        rate=1000.0, seed=args.seed, deadline=args.deadline)
    events += make_churn_trace(
        [("dyn0", dyn.base)], num_events=queries // 2, rate=1000.0,
        mutate_frac=0.25, p2p_frac=0.3, seed=args.seed + 1,
        hot_seed=args.seed + 101)
    events.sort(key=lambda e: e.arrival)

    # serial reference rows, memoized per (graph, version, source);
    # dynamic versions are immutable once committed, so verifying each
    # tick's answers at the then-current version is exact.
    rows: dict = {}

    def serial_row(graph: str, source: int) -> np.ndarray:
        if graph == "dyn0":
            key = (graph, dyn.version, source)
            g = dyn.snapshot() if key not in rows else None
        else:
            key = (graph, 0, source)
            g = graphs_by_name[graph]
        if key not in rows:
            rows[key] = shortest_paths(g, source, engine="serial").dist
        return rows[key]

    def check_tick(out) -> None:
        for a in out:
            q = a.query
            if a.status not in STATUSES:
                raise SystemExit(f"unknown status {a.status!r} for {q}")
            if a.status != STATUS_OK or a.via == "mutate" or not a.exact:
                continue
            ref = serial_row(q.graph, q.source)
            if q.target is None:
                if not np.array_equal(a.value, ref):
                    raise SystemExit(f"row mismatch vs serial: {q} "
                                     f"(via {a.via})")
            else:
                got, want = np.float32(a.value), ref[q.target]
                if not (got == want or (np.isinf(got) and np.isinf(want))):
                    raise SystemExit(
                        f"dist mismatch vs serial: {q} (via {a.via}): "
                        f"served {got!r}, serial {want!r}")

    answers, rejected, i = [], 0, 0
    submitted = 0
    max_iters = 8 * len(events) + 256   # progress backstop (backoff ticks)
    iters = 0
    while i < len(events) or sched.pending:
        iters += 1
        if iters > max_iters:
            raise SystemExit(
                f"chaos replay made no progress: {sched.pending} pending "
                f"after {iters} ticks")
        now = events[i].arrival if i < len(events) else events[-1].arrival
        chunk = 0
        while i < len(events) and chunk < 8:
            e = events[i]
            now = e.arrival
            try:
                if isinstance(e, MutationEvent):
                    sched.submit_mutation(e.graph, e.op, e.u, e.v, e.w,
                                          arrival=e.arrival)
                else:
                    sched.submit(e.graph, e.source, e.target,
                                 arrival=e.arrival, deadline=e.deadline)
                submitted += 1
            except QueryRejected:
                rejected += 1
            i += 1
            chunk += 1
        out = sched.tick(now)
        for a in out:
            a.done_at = now
        check_tick(out)     # verify at the tick's graph version
        answers.extend(out)

    # every accepted submission must be answered exactly once — the
    # scheduler made progress through every injected fault.
    if len(answers) != submitted:
        raise SystemExit(f"progress violation: {submitted} accepted "
                         f"submissions but {len(answers)} answers")
    statuses = Counter(a.status for a in answers)
    fired = plan.counts()
    print(f"[sssp_serve] chaos: {len(answers)} answers "
          f"({rejected} rejected at submit) | statuses {dict(statuses)} | "
          f"faults fired {fired} (probes {plan.summary()['probes']})",
          flush=True)

    # reconcile: every fired fault site must have surfaced through its
    # typed status (or, for retried transients, the exception counter).
    recon = []
    if fired["evict"] and not statuses["graph_gone"]:
        recon.append("evict fired but no graph_gone answers")
    if fired["mutate"] and not statuses["rejected"]:
        recon.append("mutate fired but no rejected mutation acks")
    if fired["clip"] and not statuses["not_converged"]:
        recon.append("clip fired but no not_converged answers")
    if sched.solve_exceptions < fired["solve"] + fired["stage"]:
        recon.append(
            f"{fired['solve']}+{fired['stage']} solve/stage faults fired "
            f"but only {sched.solve_exceptions} exceptions were caught")
    if recon:
        raise SystemExit("chaos reconciliation failed: " + "; ".join(recon))
    print(f"[sssp_serve] chaos: verified {len(rows)} distinct serial rows "
          f"bitwise; retries {sched.retries}, solve exceptions "
          f"{sched.solve_exceptions}, deadline expired "
          f"{sched.deadline_expired}; all fired sites reconciled",
          flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small graphs, short traces, verify on (CI-sized)")
    ap.add_argument("--scenario", default="all",
                    choices=("all",) + SCENARIOS)
    ap.add_argument("--n", type=int, default=None,
                    help="vertices per graph (default 10000; smoke 256)")
    ap.add_argument("--graphs", type=int, default=2,
                    help="number of registered graphs")
    ap.add_argument("--queries", type=int, default=None,
                    help="queries per scenario (default 400; smoke 60)")
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop arrival rate, queries/s "
                         "(default 500; smoke 2000)")
    ap.add_argument("--batch", type=int, default=16,
                    help="max distinct sources per tick per graph")
    ap.add_argument("--landmarks", type=int, default=8,
                    help="ALT landmarks per graph (0 disables)")
    ap.add_argument("--cache-rows", type=int, default=256)
    ap.add_argument("--devices", type=int, default=1,
                    help="mesh size for the sharded route (emulated host "
                         "devices on CPU; 1 = never shard)")
    ap.add_argument("--shard-threshold", type=int,
                    default=DEFAULT_SHARD_THRESHOLD,
                    help="route graphs with >= this many vertices through "
                         "the sharded engines (needs --devices > 1)")
    ap.add_argument("--verify", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="bitwise-check every answer vs serial "
                         "(default: on under --smoke)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-query deadline in seconds after arrival "
                         "(None = queries never expire)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded-queue admission: reject/shed submits "
                         "past this many pending queries")
    ap.add_argument("--chaos", action="store_true",
                    help="deterministic seeded fault-injection replay "
                         "(serve/faults.py); verifies every exact answer "
                         "bitwise and reconciles fired faults vs statuses")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="fault-plan seed (independent of --seed)")
    ap.add_argument("--fault-rate", type=float, default=0.1,
                    help="chaos fault-rate scale factor across sites")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="capture observability: Chrome trace JSON to "
                         "PATH, per-solve cost records to "
                         "PATH-with-.cost.jsonl; both are schema-"
                         "validated at exit (repro/obs)")
    ap.add_argument("--calibration", default=None, metavar="PATH",
                    help="serve through the measured cost model fitted "
                         "from this CALIBRATION.json (repro/tune) "
                         "instead of the hard-coded thresholds; "
                         "out-of-support queries still fall back to "
                         "them")
    args = ap.parse_args(argv)
    enable_compile_cache()
    use_devices(args.devices)

    capture = None
    if args.trace_out:
        from repro.obs import install_capture
        capture = install_capture()

    n = args.n or (256 if args.smoke else 10000)
    queries = args.queries or (60 if args.smoke else 400)
    rate = args.rate or (2000.0 if args.smoke else 500.0)
    verify = args.verify if args.verify is not None else args.smoke
    scenarios = SCENARIOS if args.scenario == "all" else (args.scenario,)
    if args.calibration:
        from repro.tune.model import load_model
        from repro.tune.select import TunedPolicy
        dispatch = TunedPolicy(load_model(args.calibration),
                               shard_threshold=args.shard_threshold,
                               nprocs=args.devices)
        print(f"[sssp_serve] tuned dispatch from {args.calibration}: "
              f"{dispatch.model.coverage()['engines']}", flush=True)
    else:
        dispatch = DispatchPolicy(shard_threshold=args.shard_threshold,
                                  nprocs=args.devices)
    set_default_policy(dispatch)    # engine="auto" callers agree with us
    if dispatch.nprocs > 1:
        print(f"[sssp_serve] sharded route: {dispatch.nprocs} devices, "
              f"threshold n>={args.shard_threshold}", flush=True)

    if args.chaos:
        run_chaos(args, dispatch)
        if capture is not None:
            _finalize_capture(capture, args.trace_out)
        print("[sssp_serve] done", flush=True)
        return

    graphs = [(f"g{i}", C.random_csr_graph(n, 3 * n, seed=args.seed + i))
              for i in range(args.graphs)]
    graphs_by_name = dict(graphs)
    sizes = [(name, cg.n) for name, cg in graphs]

    for scen in scenarios:
        # fresh serving state per scenario so metrics don't bleed across
        registry = GraphRegistry()
        cache = DistanceCache(capacity=args.cache_rows)
        sched = MicroBatchScheduler(registry, cache, max_batch=args.batch,
                                    dispatch=dispatch,
                                    max_queue=args.max_queue)
        t0 = time.perf_counter()
        for name, cg in graphs:
            registry.register(name, cg, landmarks=args.landmarks,
                              landmark_seed=args.seed)
        prep_s = time.perf_counter() - t0

        events = make_trace(scen, sizes, num_queries=queries, rate=rate,
                            seed=args.seed, deadline=args.deadline)
        answers = replay(sched, events)
        rec = LatencyRecorder()
        for a in answers:
            rec.observe(a, a.done_at)
        s, lat = sched.stats(), rec.summary()
        print(f"[sssp_serve] {scen}: {lat['queries']} queries "
              f"({args.graphs} graphs, n={n}, prep {prep_s:.2f}s) | "
              f"p50 {lat['p50_ms']:.1f} ms, p99 {lat['p99_ms']:.1f} ms, "
              f"{lat['qps']:.0f} q/s | "
              f"occupancy {s['mean_occupancy']:.2f}, "
              f"dedup saved {s['dedup_saved']}, "
              f"cache hit rate {s['cache']['hit_rate']:.2f} | "
              f"via {s['answered_via']}", flush=True)
        if "queue_p50_ms" in lat:
            # end-to-end latency split: time queued before the serving
            # tick vs time inside it (LatencyRecorder's two components)
            print(f"[sssp_serve] {scen}: queue wait "
                  f"p50 {lat['queue_p50_ms']:.1f} ms / "
                  f"p99 {lat['queue_p99_ms']:.1f} ms | service "
                  f"p50 {lat['service_p50_ms']:.1f} ms / "
                  f"p99 {lat['service_p99_ms']:.1f} ms", flush=True)
        if s["sharded_batches"] or s["sharded_p2p"]:
            print(f"[sssp_serve] {scen}: sharded route "
                  f"{s['sharded_batches']} batches + {s['sharded_p2p']} "
                  f"p2p ({s['sharded_sources']} sources, "
                  f"{s['sharded_edges']} edges relaxed) on "
                  f"{dispatch.nprocs} devices", flush=True)
        # end-of-run accounting: the cache and registry counters the
        # scheduler aggregates but the per-scenario line above elides
        c, r = s["cache"], s["registry"]
        print(f"[sssp_serve] {scen}: cache {c['hits']} hits / "
              f"{c['misses']} misses / {c['evictions']} evictions "
              f"({c['rows']}/{c['capacity']} rows) | registry "
              f"{r['graphs']} graphs, {r['bytes_in_use'] / 1e6:.1f} MB "
              f"in use (budget "
              f"{'none' if r['byte_budget'] is None else r['byte_budget']}"
              f"{', OVER' if r['over_budget'] else ''}), "
              f"{r['registered']} registered / {r['evicted']} evicted",
              flush=True)
        if (s["shed"] or s["deadline_expired"] or s["submissions_rejected"]
                or s["degraded_p2p"] or s["degraded_batch"]):
            print(f"[sssp_serve] {scen}: robustness: "
                  f"{s['submissions_rejected']} rejected at submit, "
                  f"{s['shed']} shed, {s['deadline_expired']} expired, "
                  f"{s['degraded_p2p']}+{s['degraded_batch']} degraded | "
                  f"statuses {s['answered_status']}", flush=True)
        if verify:
            # deadline / bounded-queue runs legitimately produce typed
            # failures; every exact answer must still match serial.
            allow = (("deadline_exceeded", "rejected")
                     if (args.deadline is not None
                         or args.max_queue is not None) else ())
            checked = verify_answers(answers, graphs_by_name, allow=allow)
            print(f"[sssp_serve] {scen}: verified bitwise vs serial "
                  f"({checked} distinct rows)", flush=True)

    if capture is not None:
        _finalize_capture(capture, args.trace_out)
    print("[sssp_serve] done", flush=True)


def _finalize_capture(capture, path: str) -> None:
    """Write + validate the observability artifacts; abort on schema or
    answer-chain violations so CI's obs-smoke job fails loudly."""
    from repro.obs import cost_path_for, finalize_capture

    tr, cl = capture
    errs = finalize_capture(tr, cl, path)
    print(f"[sssp_serve] trace: {len(tr.spans)} spans, "
          f"{len(tr.instants)} instants -> {path} | "
          f"{len(cl.records)} cost records -> {cost_path_for(path)}",
          flush=True)
    if errs:
        for e in errs[:20]:
            print(f"[sssp_serve] trace INVALID: {e}", flush=True)
        raise SystemExit(f"observability capture invalid "
                         f"({len(errs)} errors)")
    print("[sssp_serve] trace: schema + answer chains valid", flush=True)


if __name__ == "__main__":
    main()
