"""Public facade for the SSSP engine — the paper's three implementations
(plus beyond-paper variants) behind one call.

    from repro.core.api import shortest_paths
    res = shortest_paths(graph, source=0, engine="serial")

Engines (paper §III):
    serial             Alg. 1, O(n²) textbook loop               (paper)
    dijkstra_sharded   Alg. 2, 1-D column-parallel + MINLOC      (paper, MPI)
    bellman            Alg. 3/4 relax-to-fixpoint, jnp sweep     (paper, CUDA)
    bellman_kernel     Alg. 3/4 with the Pallas min-plus kernel  (paper, CUDA->TPU)
    bellman_sharded    fixpoint + 1 all-gather/sweep             (beyond-paper)
    multisource        batched (S, n) fixpoint                   (beyond-paper)
    bellman_csr        fixpoint, O(m) segment-min sweep on CSR   (beyond-paper)
    bellman_csr_kernel fixpoint with the Pallas padded-ELL kernel (beyond-paper)
    frontier           frontier-compacted sweeps, O(active out-degree)
                       per sweep (beyond-paper, core/frontier.py)
    frontier_kernel    same, Pallas candidate kernel (kernels/frontier_relax)
    delta_stepping     true Δ-stepping: light/heavy edge split, per-bucket
                       light pull fixpoint + one heavy push per bucket
                       (beyond-paper, core/delta_stepping.py)
    delta_stepping_kernel
                       same, fused Pallas bucket-relax kernel
                       (kernels/bucket_relax)
    multisource_csr    batched (S, n) fixpoint on CSR edges, segmented
                       min-scan sweep, no scatter            (beyond-paper)
    bellman_csr_sharded vertex-partitioned CSR fixpoint: O(m/P) local
                       segment-min + 1 all-gather/sweep (beyond-paper,
                       core/sharded_csr.py; needs a mesh)
    frontier_sharded   vertex-partitioned frontier push: per sweep the
                       devices exchange only the compacted (id, dist)
                       frontier pairs — the MPI-message analogue — and
                       each relaxes O(frontier arcs into its block)
                       (beyond-paper, core/sharded_csr.py; needs a mesh)
    multisource_csr_sharded
                       batched (S, n) union-frontier push on the same
                       partition: the S sources share one compacted
                       frontier exchange and one arc-window gather per
                       sweep, so edge loads amortize S ways on top of
                       the P-way split (beyond-paper; needs a mesh)

    ``engine="auto"`` delegates the choice to the serving layer's
    dispatch policy (serve/dispatch.py): graphs at or above its
    shard-threshold route to the sharded CSR engines on a cached
    host-device mesh, everything else to the single-device frontier /
    multisource engines.  Same bitwise answers either way.

Choosing dense vs CSR vs frontier (the paper's Table I vs Table II
trade-off, plus its §V "every edge, every sweep" complaint):
    The dense engines sweep the n² adjacency matrix per relaxation, so
    their cost depends on n only — ideal for dense graphs (Table I, m ≈
    n²/2) where the matrix *is* the edge set.  For sparse graphs (Table II,
    m = 3n) the matrix is ~n/6 times larger than the edges and the paper's
    §V flags exactly this as its memory/perf ceiling (40k vertices = 1.6 GB
    dense).  The ``bellman_csr*`` engines store O(n + m) and do O(m) work
    per sweep: prefer them whenever m << n², and use a ``CsrGraph``
    (core/csr.py) directly to skip the dense matrix entirely.  Dense
    ``Graph`` inputs are auto-converted; ``CsrGraph`` inputs passed to a
    dense engine are densified (O(n²) — only sensible for small n).
    Caveat: ``bellman_csr_kernel`` builds the padded-ELL view, which is
    O(n · max_in_degree) — on heavily skewed graphs (a hub vertex with ~n
    incoming arcs) that re-approaches O(n²); use ``bellman_csr`` (flat
    segment-min, strictly O(n + m)) for such degree distributions.

    The ``frontier*`` engines go one step further: each sweep relaxes only
    the out-edges of vertices whose distance improved last sweep, so
    per-sweep work is O(frontier out-degree) instead of O(m).  They win
    whenever frontiers stay narrow relative to the edge set — long-diameter
    sparse graphs (road-network-like, the Table II shape at large n), where
    late sweeps of ``bellman_csr`` touch all m arcs to improve a handful of
    vertices.  They *lose* on dense diameter-2 graphs (Table I): there the
    first frontier is essentially every vertex, so compaction adds overhead
    while the sweep still touches ~every edge — keep ``bellman`` /
    ``bellman_csr`` for those.  On heavy-tailed weight distributions pass
    ``delta=`` to bucket the frontier Δ-stepping-style.  ``SsspResult.
    edges_relaxed`` reports the measured relaxation work for all CSR-family
    engines (benchmarks/run_bench.py tracks the ratio as a perf gate).

    The ``delta_stepping*`` engines are the full Δ-stepping algorithm, not
    the frontier engine's bucket throttle: edges are split once by weight
    at staging (light <= Δ as a padded in-ELL, heavy > Δ as an outgoing
    CSR), each bucket's light arcs iterate to a fixpoint via a fused dense
    PULL (no per-sweep frontier compaction at all), and each settled
    bucket's heavy arcs are pushed exactly once.  They win where the
    frontier engine's per-sweep ``nonzero`` compaction dominates — long-
    diameter graphs (road-like grids: hundreds of frontier sweeps collapse
    into a handful of bucket phases) and heavy-tailed weight mixes (hub
    fan-outs relaxed once per bucket instead of per sweep).  They lose
    when the light in-ELL is wide (dense or hub-in-degree-skewed graphs —
    the pull does O(n·K_light) work per pass; ``delta_profile`` reports
    ``routable=False`` and serve/dispatch.py keeps the frontier engine).
    Distances stay bitwise-equal to ``serial`` for ANY positive Δ; Δ only
    moves work between phases.  ``delta="auto"`` (also the delta engines'
    default) picks Δ per graph from the weight distribution
    (core/delta_stepping.auto_delta — deterministic, memoized).  For these
    engines ``sweeps`` counts outer bucket phases and ``edges_relaxed``
    charges every light pass at the full light arc count — honest
    accounting for the pull's regular-but-total touch pattern.

    ``multisource_csr`` batches S sources over one shared edge gather per
    sweep (the sparse twin of ``multisource``): use it to amortize the
    edge-index loads when solving many sources on one sparse graph.  Like
    ``multisource`` it returns ``pred=None``; :func:`recover_pred` rebuilds
    the predecessor rows on demand at O(m) per source.

Dense vs sparse partitioning (the sharded engines' trade-off):
    The dense sharded engines (``dijkstra_sharded``/``bellman_sharded``/
    ``multisource``) split the O(n²) adjacency matrix into column slabs —
    each device stores n²/P entries however sparse the graph, which is the
    paper's own §V ceiling merely divided by P.  The CSR sharded engines
    partition the *vertices* and give each device only the O(m/P) arcs
    targeting its block (``CsrGraph.partitioned``), so sparse graphs shard
    at sparse cost; the dense slabs remain the right choice only when the
    matrix is the edge set (Table I density).  Within the CSR pair:
    ``bellman_csr_sharded`` moves O(n) per sweep (the gathered distance
    vector) and touches every local arc; ``frontier_sharded`` moves only
    the compacted frontier pairs and touches only frontier arcs — wins
    whenever frontiers are narrow (long-diameter sparse graphs), loses the
    exchange overhead when the frontier is ~everything (dense diameter-2
    graphs, where ``bellman_csr_sharded``'s single collective is cheaper).
    Both report ``edges_relaxed``; benchmarks/run_bench.py gates
    ``frontier_sharded`` at P=4 against single-device ``frontier`` (same
    work, partitioned — each arc has exactly one owner).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import csr as csr_mod
from repro.core import graph as graph_mod
from repro.core.bellman import (predecessors_from_dist, sssp_bellman,
                                sssp_bellman_sharded)
from repro.core.bellman_csr import (csr_operands, predecessors_from_dist_csr,
                                    sssp_bellman_csr, sssp_multisource_csr)
from repro.core.delta_stepping import (auto_delta, delta_operands,
                                       sssp_delta_stepping)
from repro.core.frontier import frontier_operands, sssp_frontier
from repro.core.multisource import sssp_multisource, sssp_multisource_sharded
from repro.core.serial import dijkstra_serial
from repro.core.sharded import dijkstra_sharded

ENGINES = (
    "serial",
    "dijkstra_sharded",
    "bellman",
    "bellman_kernel",
    "bellman_sharded",
    "multisource",
    "bellman_csr",
    "bellman_csr_kernel",
    "frontier",
    "frontier_kernel",
    "delta_stepping",
    "delta_stepping_kernel",
    "multisource_csr",
    "bellman_csr_sharded",
    "frontier_sharded",
    "multisource_csr_sharded",
)

# single-source engines that consume CsrGraph operands natively (and return
# a pred tree); multisource_csr also runs on CSR but is batched/pred-less.
CSR_ENGINES = ("bellman_csr", "bellman_csr_kernel",
               "frontier", "frontier_kernel")
FRONTIER_ENGINES = ("frontier", "frontier_kernel")
# true Δ-stepping engines (core/delta_stepping.py): light/heavy split,
# bucketed schedule; delta= selects the bucket width ("auto" = per-graph)
DELTA_ENGINES = ("delta_stepping", "delta_stepping_kernel")
# every engine that consumes (rather than ignores) the delta= argument
_DELTA_CONSUMERS = FRONTIER_ENGINES + DELTA_ENGINES
# mesh-requiring engines on vertex-partitioned CSR blocks (core/sharded_csr)
SHARDED_CSR_ENGINES = ("bellman_csr_sharded", "frontier_sharded",
                       "multisource_csr_sharded")
# every engine that consumes CsrGraph input without densifying it
_CSR_NATIVE = (CSR_ENGINES + DELTA_ENGINES + ("multisource_csr",)
               + SHARDED_CSR_ENGINES)


@dataclasses.dataclass
class SsspResult:
    dist: np.ndarray            # (n,) or (S, n)
    pred: Optional[np.ndarray]  # (n,) or None (recover_pred rebuilds it)
    sweeps: Optional[int]       # fixpoint engines only
    engine: str
    # measured relaxation work, CSR-family engines only: the frontier
    # engines count actual frontier out-degrees; bellman_csr* relax all
    # nnz arcs every sweep.  The run_bench.py perf gate diffs these.
    edges_relaxed: Optional[int] = None
    # sources as submitted (multisource engines), for recover_pred.
    sources: Optional[np.ndarray] = None
    # solver guardrail, fixpoint families (bellman_csr*, frontier*,
    # multisource_csr, the sharded CSR trio, and the dynamic solves):
    # False means a max_sweeps= cap stopped the loop before the fixpoint
    # and dist may sit above the true distances — callers must not treat
    # such a result as exact (serve/errors.NotConverged is the serving
    # consumer).  None for engines without the flag (serial, dense).
    converged: Optional[bool] = None
    # doubling steps of each sweep's segmented min-scan (multisource_csr)
    scan_steps: int = 0


def _edge_count(g) -> int:
    """Cheap arc count for observability payloads: exact for CSR/dynamic
    inputs, 0 for dense (counting finite off-diagonals would cost O(n²))."""
    from repro.dynamic.overlay import DynamicGraph

    if isinstance(g, DynamicGraph):
        return int(g.nnz_live)
    if isinstance(g, csr_mod.CsrGraph):
        return int(g.nnz)
    return 0


def _resolve_auto(g, source, *, target):
    """Resolve ``engine="auto"`` through the serving layer's one dispatch
    seam (serve/dispatch.py): the process-default policy picks the engine
    and its mesh; the caller's own ``delta=`` passes through unchanged.
    Lazy import keeps core free of a hard serve dependency.  Returns the
    concrete ``(engine, mesh, axis)``."""
    from repro.serve.dispatch import default_policy

    multi = np.ndim(source) > 0
    choice = default_policy().choose(
        g, kind="batch" if multi else ("p2p" if target is not None
                                       else "single"))
    return choice.engine, choice.mesh, choice.axis


def shortest_paths(
    g: "graph_mod.Graph | csr_mod.CsrGraph | jax.Array | np.ndarray",
    source,
    *,
    engine: str = "serial",
    mesh: Optional[jax.sharding.Mesh] = None,
    axis: str = "data",
    block: int = 256,
    max_sweeps: int | None = None,
    delta: Union[float, str, None] = None,
    target: int | None = None,
    target_lb: float | None = None,
) -> SsspResult:
    """Observability shim over :func:`_shortest_paths` (the real facade,
    same signature + docs).  When a tracer or cost log is installed
    (repro/obs), every solve runs inside a ``solve`` span and emits one
    per-solve cost record (engine, n, m, sweeps, edges_relaxed, wall_ms);
    when both are disabled this adds two attribute reads and one branch.
    """
    from repro.obs.profile import get_cost_log
    from repro.obs.trace import get_tracer

    tr = get_tracer()
    cl = get_cost_log()
    if not (tr.enabled or cl.enabled):
        return _shortest_paths(g, source, engine=engine, mesh=mesh,
                               axis=axis, block=block,
                               max_sweeps=max_sweeps, delta=delta,
                               target=target, target_lb=target_lb)

    import time as _time

    # resolve "auto" HERE so the record carries the routed engine's real
    # mesh arity — the facade below passes the already-concrete engine
    # straight through.
    if engine == "auto":
        engine, mesh, axis = _resolve_auto(g, source, target=target)
    kw = dict(engine=engine, mesh=mesh, axis=axis, block=block,
              max_sweeps=max_sweeps, delta=delta, target=target,
              target_lb=target_lb)
    m = _edge_count(g)
    t0 = _time.perf_counter()
    with tr.span("solve", engine=engine) as sp:
        res = _shortest_paths(g, source, **kw)
        wall_ms = (_time.perf_counter() - t0) * 1e3
        n = int(np.shape(res.dist)[-1])
        batch = int(np.shape(res.dist)[0]) if np.ndim(res.dist) == 2 else 1
        sweeps = 0 if res.sweeps is None else int(res.sweeps)
        edges = 0 if res.edges_relaxed is None else int(res.edges_relaxed)
        conv = True if res.converged is None else bool(res.converged)
        sp.set(engine=res.engine, n=n, m=m, batch=batch, sweeps=sweeps,
               scan_steps=res.scan_steps, edges_relaxed=edges,
               converged=conv)
    nprocs = (int(mesh.devices.size)
              if mesh is not None and res.engine in SHARDED_CSR_ENGINES
              else 1)
    # the Δ the solve actually used: an explicit width verbatim; the
    # delta engines' None/"auto" resolves per graph via the memoized
    # auto_delta (identical to what the facade resolved); 0.0 otherwise.
    if isinstance(delta, (int, float)) and not isinstance(delta, bool):
        dval = float(delta)
    elif (res.engine in DELTA_ENGINES
          and isinstance(g, csr_mod.CsrGraph)):
        dval = float(auto_delta(g))
    else:
        dval = 0.0
    cl.emit(engine=res.engine, n=n, m=m, batch=batch, nprocs=nprocs,
            delta=dval, sweeps=sweeps, scan_steps=res.scan_steps,
            edges_relaxed=edges, wall_ms=wall_ms, converged=conv)
    return res


def _shortest_paths(
    g: "graph_mod.Graph | csr_mod.CsrGraph | jax.Array | np.ndarray",
    source,
    *,
    engine: str = "serial",
    mesh: Optional[jax.sharding.Mesh] = None,
    axis: str = "data",
    block: int = 256,
    max_sweeps: int | None = None,
    delta: Union[float, str, None] = None,
    target: int | None = None,
    target_lb: float | None = None,
) -> SsspResult:
    """Run one SSSP engine.  ``source`` is an int (or int array for
    ``multisource`` / ``multisource_csr``).  Sharded engines need a
    ``mesh``; the adjacency is padded to the mesh-axis size automatically
    (paper §III-B.2).

    ``delta`` sets the Δ-bucket width for the engines that consume it —
    the frontier engines' bucket throttle and the ``delta_stepping*``
    engines' light/heavy split (see the module docstring for when each
    wins).  It must be a positive finite number or the string ``"auto"``
    (resolve per graph via core/delta_stepping.auto_delta; for the delta
    engines ``None`` also means auto, since they cannot run without a Δ).
    Nonpositive, non-finite, or non-numeric values raise ``ValueError``
    eagerly — a nonpositive Δ would make every edge heavy and the bucket
    window empty — as does passing ``delta=`` to any engine that would
    silently ignore it.  Note the frontier engines compile Δ in as a
    static argument (their schedule branches on it at trace time), so
    ``"auto"``'s per-graph values recompile per graph there; the delta
    engines trace Δ as a runtime scalar and recompile only per graph
    shape.

    ``target=`` (frontier engines only) turns the solve into a
    point-to-point query with an early exit: the fixpoint loop stops as
    soon as ``dist[target]`` is provably final — with nonnegative weights,
    once no pending vertex's label is below the target's, no relaxation
    sequence can improve it (the Dijkstra settled-set argument).  The
    returned ``dist[target]`` is bitwise-equal to the full solve's, as is
    every entry with ``dist < dist[target]``; entries above it may still
    sit above their fixpoint, so a target result is a *partial* solve:
    its ``pred`` is ``None`` (a part-invalid tree is never recovered)
    and its row must not be cached as if it were complete
    (serve/scheduler.py treats it accordingly).
    ``target_lb=`` optionally sharpens the exit with an admissible lower
    bound on the s→t distance (e.g. a serve/landmarks.py ALT bound): the
    loop additionally stops once ``dist[target] <= target_lb``.  The bound
    MUST be admissible (never above the true distance) or exactness is
    lost; too-small bounds are merely inert.  ``SsspResult.edges_relaxed``
    and ``sweeps`` report the actual (reduced) work, which is what
    benchmarks/serve_bench.py measures for the point-to-point scenario.
    """
    if engine == "auto":
        # the serving layer's one dispatch seam (serve/dispatch.py) picks
        # the engine and its mesh.
        engine, mesh, axis = _resolve_auto(g, source, target=target)
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; choose from {ENGINES}")
    # Δ validation is EAGER (before any staging): a bad width would
    # otherwise surface as a silently-ignored kwarg or a hung bucket loop.
    if delta is not None:
        if engine not in _DELTA_CONSUMERS:
            raise ValueError(
                f"delta= is consumed only by {_DELTA_CONSUMERS}; engine "
                f"{engine!r} would silently ignore it")
        if delta != "auto":
            try:
                delta = float(delta)
            except (TypeError, ValueError):
                raise ValueError(
                    f"delta must be a positive finite number or 'auto', "
                    f"got {delta!r}") from None
            if not (math.isfinite(delta) and delta > 0):
                raise ValueError(
                    f"delta must be positive and finite, got {delta!r}")
    # target= early exit is frontier-only; frontier_sharded accepts target=
    # too but runs the FULL fixpoint (its row is a superset of the partial
    # solve, dist[target] bitwise-identical — serve caches it as complete).
    if target is not None and engine not in FRONTIER_ENGINES + (
            "frontier_sharded",):
        raise ValueError(
            f"target= early exit needs a frontier engine "
            f"{FRONTIER_ENGINES}; got {engine!r}")

    from repro.dynamic.overlay import DynamicGraph  # local: dynamic uses api

    if isinstance(g, DynamicGraph):
        # facade convenience: solve the CURRENT version via its snapshot
        # CSR (exact by construction).  The overlay-native engines — which
        # skip the snapshot and keep the jit cache warm across versions —
        # live in dynamic/repair.py (solve_dynamic / repair_sssp) and are
        # what the serving layer uses.
        g = g.snapshot()

    if isinstance(g, csr_mod.CsrGraph):
        cg, n_true = g, g.n
        if engine not in _CSR_NATIVE:
            # dense engines need the matrix; O(n²), small-n convenience only.
            g = cg.to_dense()
    else:
        if isinstance(g, graph_mod.Graph):
            n_true = g.n
        else:
            adj_np = np.asarray(g)
            n_true = adj_np.shape[0]
            g = graph_mod.Graph(adj=adj_np.astype(np.float32), n=n_true)
        cg = None

    if engine in SHARDED_CSR_ENGINES:
        if mesh is None:
            raise ValueError(f"engine {engine!r} needs a mesh")
        from repro.core._axes import axis_size
        from repro.core.sharded_csr import (sssp_bellman_csr_sharded,
                                            sssp_frontier_sharded,
                                            sssp_multisource_csr_sharded)

        if cg is None:
            cg = g.to_csr()
        parts = cg.partitioned(axis_size(mesh, axis))
        if engine == "multisource_csr_sharded":
            srcs = jnp.atleast_1d(jnp.asarray(source, jnp.int32))
            D, s, e, c = sssp_multisource_csr_sharded(
                parts, srcs, mesh, axis=axis, max_sweeps=max_sweeps
            )
            return SsspResult(np.asarray(D)[:, :n_true], None, int(s),
                              engine, edges_relaxed=int(e),
                              sources=np.asarray(srcs), converged=bool(c))
        if engine == "bellman_csr_sharded":
            d, p, s, c = sssp_bellman_csr_sharded(
                parts, source, mesh, axis=axis, max_sweeps=max_sweeps
            )
            # actual partitioned work: every owner sweeps its padded block.
            edges = int(s) * parts.nprocs * parts.nnz_max
            return SsspResult(np.asarray(d)[:n_true], np.asarray(p)[:n_true],
                              int(s), engine, edges_relaxed=edges,
                              converged=bool(c))
        d, s, e, c = sssp_frontier_sharded(
            parts, source, mesh, axis=axis, max_sweeps=max_sweeps
        )
        dist = jnp.asarray(d)[:n_true]
        # fixpoint pred is a pure function of (dist, graph): reuse the O(m)
        # single-device recovery, same tie-breaks as every other engine.
        pred = predecessors_from_dist_csr(dist, csr_operands(cg),
                                          jnp.int32(source))
        return SsspResult(np.asarray(dist), np.asarray(pred), int(s), engine,
                          edges_relaxed=int(e), converged=bool(c))

    if engine in DELTA_ENGINES:
        if cg is None:
            cg = g.to_csr()
        # None and "auto" both resolve per graph: the engine cannot run
        # without a width, and auto_delta is deterministic + memoized.
        dval = auto_delta(cg) if delta in (None, "auto") else delta
        operands = delta_operands(cg, dval)
        pull_fn = None
        if engine == "delta_stepping_kernel":
            from repro.kernels.bucket_relax.ops import make_bucket_pull_fn

            pull_fn = make_bucket_pull_fn(block_v=block)
        d, p, s, e, c = sssp_delta_stepping(
            operands,
            jnp.int32(source),
            jnp.float32(dval),
            n=cg.n,
            pull_fn=pull_fn,
            max_sweeps=max_sweeps,
        )
        return SsspResult(np.asarray(d), np.asarray(p), int(s), engine,
                          edges_relaxed=int(e), converged=bool(c))

    if engine in FRONTIER_ENGINES:
        if cg is None:
            cg = g.to_csr()
        if delta == "auto":
            delta = auto_delta(cg)
        use_kernel = engine == "frontier_kernel"
        operands = frontier_operands(cg, with_ell=use_kernel)
        sweep_fn = None
        if use_kernel:
            from repro.kernels.frontier_relax.ops import make_frontier_sweep_fn

            sweep_fn = make_frontier_sweep_fn(block_f=block)
        d, p, s, e, c = sssp_frontier(
            operands,
            jnp.int32(source),
            n=cg.n,
            sweep_fn=sweep_fn,
            max_sweeps=max_sweeps,
            delta=delta,
            target=None if target is None else jnp.int32(target),
            target_lb=None if target_lb is None else jnp.float32(target_lb),
        )
        # target= solves return pred=None: the partial row's tree would be
        # part-invalid (see the target docs above), and skipping the O(m)
        # recovery is the point of the early exit.
        return SsspResult(np.asarray(d),
                          None if p is None else np.asarray(p), int(s),
                          engine, edges_relaxed=int(e), converged=bool(c))

    if engine == "multisource_csr":
        if cg is None:
            cg = g.to_csr()
        srcs = jnp.atleast_1d(jnp.asarray(source, jnp.int32))
        D, s, c = sssp_multisource_csr(
            csr_operands(cg), srcs, n=cg.n, max_sweeps=max_sweeps
        )
        return SsspResult(np.asarray(D), None, int(s), engine,
                          edges_relaxed=int(s) * cg.nnz * srcs.shape[0],
                          sources=np.asarray(srcs), converged=bool(c),
                          scan_steps=cg.in_runs()[2])

    if engine in CSR_ENGINES:
        if cg is None:
            cg = g.to_csr()
        use_kernel = engine == "bellman_csr_kernel"
        operands = csr_operands(cg, with_ell=use_kernel)
        sweep_fn = None
        if use_kernel:
            from repro.kernels.csr_relax.ops import make_csr_sweep_fn

            sweep_fn = make_csr_sweep_fn(block_v=block)
        d, p, s, c = sssp_bellman_csr(
            operands,
            jnp.int32(source),
            n=cg.n,
            sweep_fn=sweep_fn,
            max_sweeps=max_sweeps,
        )
        return SsspResult(np.asarray(d), np.asarray(p), int(s), engine,
                          edges_relaxed=int(s) * cg.nnz, converged=bool(c))

    if engine == "serial":
        d, p = dijkstra_serial(jnp.asarray(g.adj), jnp.int32(source))
        return SsspResult(np.asarray(d), np.asarray(p), None, engine)

    if engine == "bellman":
        d, p, s = sssp_bellman(
            jnp.asarray(g.adj), jnp.int32(source), max_sweeps=max_sweeps
        )
        return SsspResult(np.asarray(d), np.asarray(p), int(s), engine)

    if engine == "bellman_kernel":
        from repro.kernels.sssp_relax.ops import make_sweep_fn

        d, p, s = sssp_bellman(
            jnp.asarray(g.adj),
            jnp.int32(source),
            sweep_fn=make_sweep_fn(block_u=block, block_v=block),
            max_sweeps=max_sweeps,
        )
        return SsspResult(np.asarray(d), np.asarray(p), int(s), engine)

    if engine == "multisource":
        srcs = jnp.atleast_1d(jnp.asarray(source, jnp.int32))
        if mesh is not None:
            gp = g.padded(mesh.shape[axis])
            D, s = sssp_multisource_sharded(
                jnp.asarray(gp.adj), srcs, mesh, axis=axis, max_sweeps=max_sweeps
            )
            return SsspResult(np.asarray(D)[:, :n_true], None, int(s), engine,
                              sources=np.asarray(srcs))
        D, s = sssp_multisource(jnp.asarray(g.adj), srcs, max_sweeps=max_sweeps)
        return SsspResult(np.asarray(D), None, int(s), engine,
                          sources=np.asarray(srcs))

    # --- sharded engines -------------------------------------------------
    if mesh is None:
        raise ValueError(f"engine {engine!r} needs a mesh")
    gp = g.padded(mesh.shape[axis])

    if engine == "dijkstra_sharded":
        d, p = dijkstra_sharded(
            jnp.asarray(gp.adj), source, mesh, axis=axis, n_true=n_true
        )
        return SsspResult(
            np.asarray(d)[:n_true], np.asarray(p)[:n_true], None, engine
        )

    d, p, s = sssp_bellman_sharded(
        jnp.asarray(gp.adj), source, mesh, axis=axis, max_sweeps=max_sweeps
    )
    return SsspResult(
        np.asarray(d)[:n_true], np.asarray(p)[:n_true], int(s), engine
    )


def recover_pred(
    result: SsspResult,
    g: "graph_mod.Graph | csr_mod.CsrGraph | jax.Array | np.ndarray",
) -> np.ndarray:
    """Rebuild predecessor rows for a result that skipped them.

    The multisource engines return ``pred=None`` because at the fixpoint
    the tree is a pure function of (dist, graph) — materializing S rows
    eagerly would waste memory on callers that only need distances.  This
    reuses the same recovery helpers the single-source engines run (so the
    trees match them exactly, tie-breaks included): the O(m) segment-min
    over CSR arcs for a ``CsrGraph``, the O(n²) masked argmin for a dense
    graph.  Results that already carry a pred are returned as-is.

    Output matches ``result.dist``'s shape: (S, n) for batched results,
    (n,) for single-source.  Same validity caveat as the eager recoveries:
    a valid tree whenever edge weights are strictly positive.
    """
    if result.pred is not None:
        return result.pred
    D = jnp.atleast_2d(jnp.asarray(result.dist, jnp.float32))
    if result.sources is not None:
        srcs = jnp.atleast_1d(jnp.asarray(result.sources, jnp.int32))
    else:
        # dist[source] == 0 is each row's minimum under nonnegative weights.
        srcs = jnp.argmin(D, axis=1).astype(jnp.int32)
    if isinstance(g, csr_mod.CsrGraph):
        ops = csr_operands(g)
        P = jax.vmap(lambda d, s: predecessors_from_dist_csr(d, ops, s))(
            D, srcs
        )
    else:
        adj = jnp.asarray(g.adj if isinstance(g, graph_mod.Graph) else g,
                          jnp.float32)
        P = jax.vmap(lambda d, s: predecessors_from_dist(d, adj, s))(D, srcs)
    P = np.asarray(P)
    return P if np.ndim(result.dist) == 2 else P[0]
