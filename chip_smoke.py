"""Bring-up check: the SSSP serving path, end to end, on a TPU.

    python chip_smoke.py [--seed S]              # one chip
    python chip_smoke.py --chips 4 [--seed S]    # the sharded route only

One chip.  Generates two graphs from the seed: a 512 x 512 road grid
(262,144 vertices, ~1.05M arcs; the size of the DIMACS 9th Challenge "NY"
road network, 264,346 vertices) and the paper's Table II corpus at
n = 2^22 (m = 3n, ~25M arcs).  Both are registered with ALT landmarks in
a ``GraphRegistry`` and served through a ``MicroBatchScheduler`` under the
default ``DispatchPolicy``, as ``repro.launch.sssp_serve`` wires them:

* road: p2p ``dist(s, t)`` queries, one per tick (``frontier`` with
  ``target=``), and one ``shortest_paths(engine="auto")`` single-source
  solve (``delta_stepping``);
* Table II: one batch of full-row queries (``multisource_csr``), after the
  compiled program's memory analysis shows it fits the device.

Every answer must be ``ok`` and ``exact``.  Every answer from each graph's
first source ``s0`` is compared bitwise with an f32 heap Dijkstra on the
host; the other batch rows must pass an exact f32 fixpoint certificate and
agree with scipy's f64 Dijkstra within f32 rounding (core/host_ref.py).
Then each Pallas kernel engine runs once -- three on the road graph,
``bellman_kernel`` on a dense 16384-vertex graph -- must carry a
``tpu_custom_call`` in its lowered program, and must equal its XLA twin
bitwise.

The road grid is not larger because every engine here is a relaxation
fixpoint whose sweep count is the hop depth of the shortest-path tree:
4,255 sweeps on a 2048 x 2048 grid, at 0.12-0.3 s per sweep on a v5e
(each sweep gathers every arc's source distance), which no one-chip run
can afford.

``--chips 4`` serves the Table II corpus at n = 2^20 through the same
scheduler on the four-device serving mesh (``multisource_csr_sharded``
batches, ``frontier_sharded`` p2p), runs one ``bellman_csr_sharded``
solve, and compares each bitwise with the single-device engines
(``multisource_csr``, ``bellman_csr``) in the same process.

The script refuses to run (nonzero exit, no result line) without a TPU or
with fewer devices than asked; any failed check raises.  A passing run ends
with one JSON line: ``{"ok": true, "device": {"platform": "tpu", "kind":
..., "count": N}}``.  Phase timings, staging bytes and peak device memory
go to the lines before it.  Everything that touches JAX runs in this one
process, which holds the chip; the host references run meanwhile in
worker processes that never import JAX.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import multiprocessing
import os
import sys
import time
from concurrent.futures import ProcessPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROAD_N = 512 ** 2
SPARSE_N = 2 ** 22
SHARDED_N = 2 ** 20     # --chips 4
DENSE_N = 16384
LANDMARKS = 2
BATCH = 4               # distinct full-row sources: one bucket
MAX_BATCH = 16
P2P = 3                 # p2p queries on the road graph
P2P_SHARDED = 2
# lowering and XLA compilation (or its persistent-cache load); tracing
# nests one jit inside another, so it is left in the run time
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Clock:
    """Wall time per phase, split into compile time (JAX's own compile
    events, set-up) and the rest (solve, host work)."""

    def __init__(self, jax):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.compile_s += duration

    @contextlib.contextmanager
    def phase(self, name: str):
        t0, c0 = time.perf_counter(), self.compile_s
        yield
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        log(f"phase {name}: wall {wall:.3f} s = compile {comp:.3f} s "
            f"+ run {wall - comp:.3f} s")


def require(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def same(a, b) -> bool:
    """Bitwise equality of two distance arrays (inf == inf)."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return a.shape == b.shape and a.tobytes() == b.tobytes()


def served(answers, via: str) -> list:
    """Every answer must be ok and exact, from the expected path."""
    for a in answers:
        require(a.ok and a.exact,
                f"{a.query}: status {a.status} exact {a.exact}: {a.error}")
        require(a.via == via, f"{a.query} answered via {a.via}, not {via}")
    return answers


def pick_sources(rng, n: int, k: int, avoid) -> list:
    """``k`` distinct vertices outside ``avoid`` (landmark rows would be
    answered without an engine)."""
    out: list = []
    avoid = set(int(v) for v in avoid)
    while len(out) < k:
        v = int(rng.integers(n))
        if v not in avoid:
            avoid.add(v)
            out.append(v)
    return out


def make_graphs(clock, seed: int, sizes: dict) -> dict:
    from repro.core import csr as C

    makers = {"road": C.road_like_csr_graph, "sparse": C.sparse_csr_graph}
    graphs = {}
    with clock.phase("generate"):
        for name, n in sizes.items():
            graphs[name] = cg = makers[name](n, seed=seed)
            log(f"graph {name}: n {cg.n} arcs {cg.nnz} "
                f"host bytes {cg.nbytes}")
    return graphs


def register(clock, graphs, seed: int, landmarks: int):
    from repro.serve import (DispatchPolicy, DistanceCache, GraphRegistry,
                             MicroBatchScheduler, set_default_policy)

    policy = DispatchPolicy()
    set_default_policy(policy)              # engine="auto" agrees
    registry = GraphRegistry()
    sched = MicroBatchScheduler(registry, DistanceCache(capacity=64),
                                max_batch=MAX_BATCH, dispatch=policy)
    with clock.phase("register (staging + landmark solves)"):
        for name, cg in graphs.items():
            registry.register(name, cg, landmarks=landmarks,
                              landmark_seed=seed)
    for name in graphs:
        log(f"staged {name}: registry nbytes {registry.get(name).nbytes}")
    log(f"registry bytes in use {registry.bytes_in_use}")
    return policy, registry, sched


def check_batch_fits(jax, cg, bucket: int) -> None:
    """Compile the batched program for this graph and bucket from shapes
    alone and require its memory to fit the device before serving it."""
    import jax.numpy as jnp

    from repro.core.bellman_csr import sssp_multisource_csr

    arc = lambda dt: jax.ShapeDtypeStruct((cg.nnz,), dt)   # noqa: E731
    ops = {"src": arc(jnp.int32), "dst": arc(jnp.int32),
           "w": arc(jnp.float32), "rank": arc(jnp.int32),
           "last": jax.ShapeDtypeStruct((cg.n,), jnp.int32),
           "scan_steps": jax.ShapeDtypeStruct((), jnp.int32)}
    ma = sssp_multisource_csr.lower(
        ops, jax.ShapeDtypeStruct((bucket,), jnp.int32),
        n=cg.n).compile().memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    log(f"multisource_csr n {cg.n} S {bucket}: arguments "
        f"{ma.argument_size_in_bytes} + outputs {ma.output_size_in_bytes} "
        f"+ temporaries {ma.temp_size_in_bytes} = {need} bytes of "
        f"{limit}")
    require(need < 0.8 * limit, "batched program does not fit the device")


def plan(graphs, registry, seed: int) -> dict:
    """Per graph, its sources (the first, ``s0``, is checked bitwise
    against the heap reference): road gets one source and the p2p
    targets, the Table II graph the batch sources."""
    rng = np.random.default_rng(seed)
    work = {}
    for name, cg in graphs.items():
        ls = registry.get(name).landmarks.ids
        if name == "road":
            srcs = pick_sources(rng, cg.n, 1, ls)
            targets = pick_sources(rng, cg.n, P2P, list(ls) + srcs)
        else:
            srcs, targets = pick_sources(rng, cg.n, BATCH, ls), []
        work[name] = {"sources": srcs, "targets": targets}
    return work


def start_references(pool, graphs, work) -> None:
    """Start the host references in worker processes, which stay off JAX,
    so they run while the chip serves: the f32 heap Dijkstra from s0 and
    scipy's rows from the other sources."""
    from repro.core.host_ref import heap_dijkstra_f32, scipy_rows

    for name, cg in graphs.items():
        w = work[name]
        arrays = (cg.indptr, cg.indices, cg.weights, cg.n)
        w["heap_ref"] = pool.submit(heap_dijkstra_f32, *arrays,
                                    w["sources"][0])
        if len(w["sources"]) > 1:
            w["scipy_ref"] = pool.submit(scipy_rows, *arrays,
                                         w["sources"][1:])


def serve_one_chip(jax, clock, graphs, sched, work):
    """Road p2p and auto, Table II batch; records what each answered for
    the reference check."""
    from repro.core.api import shortest_paths

    road, sparse = work["road"], work["sparse"]
    s0 = road["sources"][0]
    with clock.phase(f"serve road p2p x{P2P} (frontier target=)"):
        road["p2p"] = []
        for t in road["targets"]:
            sched.submit("road", s0, t)
            road["p2p"] += served(sched.tick(), "target")
    with clock.phase("serve road auto single-source"):
        res = shortest_paths(graphs["road"], s0, engine="auto")
        require(res.converged, "auto solve on road did not converge")
        road["auto"] = res
    log(f"road auto routed to {res.engine}, {res.sweeps} sweeps")
    check_batch_fits(jax, graphs["sparse"], BATCH)
    with clock.phase(f"serve sparse batch x{BATCH} (multisource_csr)"):
        for s in sparse["sources"]:
            sched.submit("sparse", s)
        sparse["batch"] = served(sched.tick(), "batch")
    require(len(sparse["batch"]) == BATCH, "batch answers missing")
    s = sched.stats()
    require(s["engine_batches"] == 1 and s["target_solves"] == P2P,
            f"unexpected engine use: {s}")


def check_reference(clock, graphs, work) -> np.ndarray:
    """Bitwise heap-Dijkstra rows for each graph's s0; certificate + scipy
    for the other batch rows.  Returns the road s0 row for the kernel
    phase."""
    from repro.core.host_ref import check_f32_row

    heap = {}
    for name, cg in graphs.items():
        w = work[name]
        s0 = w["sources"][0]
        with clock.phase(f"reference {name}: wait for the f32 heap "
                         f"Dijkstra from {s0}"):
            heap[name] = w["heap_ref"].result()
    road, ref = work["road"], heap["road"]
    for a in road["p2p"]:
        require(np.float32(a.value) == ref[a.query.target],
                f"p2p {a.query} served {a.value!r}, "
                f"reference {ref[a.query.target]!r}")
    require(same(road["auto"].dist, ref),
            f"auto ({road['auto'].engine}) row differs on road")
    log(f"road: {P2P} p2p + auto row from {road['sources'][0]} bitwise "
        "equal to the heap reference")
    sparse, cg = work["sparse"], graphs["sparse"]
    rows = {a.query.source: a.value for a in sparse["batch"]}
    s0, others = sparse["sources"][0], sparse["sources"][1:]
    require(same(rows[s0], heap["sparse"]), f"batch row {s0} differs")
    with clock.phase(f"reference sparse: scipy x{len(others)} + "
                     "certificates"):
        D, Pr = sparse["scipy_ref"].result()
        for i, s in enumerate(others):
            check_f32_row(cg.indptr, cg.indices, cg.weights, cg.n, s,
                          rows[s], D[i], Pr[i])
    log(f"sparse: batch row {s0} bitwise equal to the heap reference; "
        f"{len(others)} rows certified")
    return ref


def lowered_has_kernel(fn, *args, **kw) -> bool:
    return "tpu_custom_call" in fn.lower(*args, **kw).as_text()


def check_kernels(jax, clock, road, s0, heap_row, auto, seed: int) -> None:
    """Each *_kernel engine once, compiled (not interpreted) and bitwise
    equal to its XLA twin."""
    import jax.numpy as jnp

    from repro.core import graph as G
    from repro.core.api import shortest_paths
    from repro.core.bellman import sssp_bellman
    from repro.core.bellman_csr import csr_operands, sssp_bellman_csr
    from repro.core.delta_stepping import (auto_delta, delta_operands,
                                           sssp_delta_stepping)
    from repro.core.frontier import frontier_operands, sssp_frontier
    from repro.kernels.bucket_relax.ops import make_bucket_pull_fn
    from repro.kernels.csr_relax.ops import make_csr_sweep_fn
    from repro.kernels.frontier_relax.ops import make_frontier_sweep_fn
    from repro.kernels.sssp_relax.ops import make_sweep_fn

    src = jnp.int32(s0)
    dval = auto_delta(road)
    require(lowered_has_kernel(
        sssp_bellman_csr, csr_operands(road, with_ell=True), src, n=road.n,
        sweep_fn=make_csr_sweep_fn(block_v=256)), "bellman_csr_kernel")
    require(lowered_has_kernel(
        sssp_frontier, frontier_operands(road, with_ell=True), src,
        n=road.n, sweep_fn=make_frontier_sweep_fn(block_f=256)),
        "frontier_kernel")
    require(lowered_has_kernel(
        sssp_delta_stepping, delta_operands(road, dval), src,
        jnp.float32(dval), n=road.n, pull_fn=make_bucket_pull_fn(
            block_v=256)), "delta_stepping_kernel")
    log("lowered programs of the three CSR kernel engines hold a "
        "tpu_custom_call")
    twins = {"bellman_csr_kernel": "bellman_csr",
             "frontier_kernel": "frontier",
             "delta_stepping_kernel": "delta_stepping"}
    for kern, xla in twins.items():
        runs = {}
        for eng in (kern, xla):
            if eng == auto.engine:
                runs[eng] = auto
                continue
            with clock.phase(f"kernels road {eng}"):
                runs[eng] = shortest_paths(road, s0, engine=eng)
            require(runs[eng].converged, f"{eng} did not converge")
        k, x = runs[kern], runs[xla]
        require(same(k.dist, x.dist) and np.array_equal(k.pred, x.pred),
                f"{kern} differs from {xla}")
        require(same(k.dist, heap_row), f"{kern} differs from the heap row")
        log(f"{kern} == {xla} bitwise ({k.sweeps} / {x.sweeps} sweeps)")

    with clock.phase(f"generate dense n {DENSE_N}"):
        dense = G.random_graph(DENSE_N, 3 * DENSE_N, seed=seed)
    require(lowered_has_kernel(
        sssp_bellman, jnp.asarray(dense.adj), jnp.int32(0),
        sweep_fn=make_sweep_fn(block_u=256, block_v=256)), "bellman_kernel")
    runs = {}
    for eng in ("bellman_kernel", "bellman"):
        with clock.phase(f"kernels dense {eng}"):
            runs[eng] = shortest_paths(dense, 0, engine=eng)
    require(same(runs["bellman_kernel"].dist, runs["bellman"].dist)
            and np.array_equal(runs["bellman_kernel"].pred,
                               runs["bellman"].pred),
            "bellman_kernel differs from bellman")
    log(f"bellman_kernel == bellman bitwise on the dense graph "
        f"({runs['bellman'].sweeps} sweeps); lowered program holds a "
        f"tpu_custom_call")


def run_one_chip(jax, clock, seed: int) -> None:
    graphs = make_graphs(clock, seed, {"road": ROAD_N, "sparse": SPARSE_N})
    _, registry, sched = register(clock, graphs, seed, LANDMARKS)
    work = plan(graphs, registry, seed)
    spawn = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(3, mp_context=spawn) as pool:
        start_references(pool, graphs, work)
        serve_one_chip(jax, clock, graphs, sched, work)
        heap_row = check_reference(clock, graphs, work)
    road = work["road"]
    check_kernels(jax, clock, graphs["road"], road["sources"][0], heap_row,
                  road["auto"], seed)


def run_four_chips(jax, clock, seed: int) -> None:
    """The sharded route, and the single-device engines it must equal."""
    from repro.core.api import shortest_paths
    from repro.serve.dispatch import serving_mesh

    cg = make_graphs(clock, seed, {"sparse": SHARDED_N})["sparse"]
    policy, registry, sched = register(clock, {"sparse": cg}, seed, 0)
    require(policy.nprocs == 4 and policy.would_shard(cg.n),
            f"policy does not shard n {cg.n} over {policy.nprocs} devices")
    rng = np.random.default_rng(seed)
    srcs = pick_sources(rng, cg.n, BATCH + P2P_SHARDED, ())
    batch_srcs, p2p_srcs = srcs[:BATCH], srcs[BATCH:]
    targets = pick_sources(rng, cg.n, P2P_SHARDED, srcs)
    with clock.phase(f"serve sparse batch x{BATCH} "
                     "(multisource_csr_sharded)"):
        for s in batch_srcs:
            sched.submit("sparse", s)
        batch = served(sched.tick(), "batch")
    with clock.phase(f"serve sparse p2p x{P2P_SHARDED} (frontier_sharded)"):
        p2p = []
        for s, t in zip(p2p_srcs, targets):
            sched.submit("sparse", s, t)
            p2p += served(sched.tick(), "target")
    st = sched.stats()
    require(st["sharded_batches"] == 1 and st["sharded_p2p"] == P2P_SHARDED,
            f"sharded route not taken: {st}")
    with clock.phase("bellman_csr_sharded"):
        bell4 = shortest_paths(cg, batch_srcs[0],
                               engine="bellman_csr_sharded",
                               mesh=serving_mesh(4))
    with clock.phase(f"single-device twins: multisource_csr x{len(srcs)}, "
                     "bellman_csr"):
        multi = shortest_paths(cg, np.asarray(srcs), engine="multisource_csr")
        bell1 = shortest_paths(cg, batch_srcs[0], engine="bellman_csr")
    rows = dict(zip(srcs, multi.dist))
    for a in batch:
        require(same(a.value, rows[a.query.source]),
                f"sharded batch row {a.query.source} differs")
    for a in p2p:
        s, t = a.query.source, a.query.target
        row = sched.cache.get(registry.get("sparse").row_key(s, shards=4))
        require(np.float32(a.value) == rows[s][t] and same(row, rows[s]),
                f"sharded p2p {a.query} differs from multisource_csr")
    require(same(bell4.dist, bell1.dist)
            and np.array_equal(bell4.pred, bell1.pred),
            "bellman_csr_sharded differs from bellman_csr")
    log(f"sharded == single-device bitwise: {BATCH} batch rows, "
        f"{P2P_SHARDED} p2p rows, bellman_csr ({bell4.sweeps} sweeps)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded route on a four-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s); JAX sees "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.runtime import enable_compile_cache

    log(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {enable_compile_cache()}")
    clock = Clock(jax)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(jax, clock, args.seed)
    else:
        run_one_chip(jax, clock, args.seed)
    peak = max(d.memory_stats()["peak_bytes_in_use"] for d in devices)
    log(f"total wall {time.perf_counter() - t0:.3f} s, of which compile "
        f"{clock.compile_s:.3f} s; peak device bytes in use {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
