"""Vertex-partitioned sparse SSSP over per-owner CSR blocks — the paper's
Algorithm 2 partitioning scheme, re-based from the dense O(n²/P) column
slabs onto O(m/P) CSR row blocks.

The paper's MPI version 1-D-partitions the *adjacency matrix*: each of the
P processes owns n/P columns and sweeps them densely, which inherits the
exact §V memory/density ceiling the single-device CSR engines (PR 1–2)
already lifted.  Here each device owns n_pad/P vertices and holds only the
arcs *targeting* its owned block (``CsrGraph.partitioned`` — incoming-CSR
row slices, the sparse analogue of the paper's column slabs), so per-device
graph memory is ~m/P and per-sweep local work is O(m/P) instead of O(n²/P).
Kainer & Träff (arXiv:1903.12085) and the Δ-stepping line (arXiv:1604.02113)
both locate scalable SSSP exactly here: partitioned sparse relaxation with
small per-round exchanges.

Two engines, both running the whole fixpoint inside one shard_map region
(one jit, collectives inside the loop):

* :func:`sssp_bellman_csr_sharded` — every sweep each owner segment-mins
  its local arcs (O(m/P)) and ONE tiled all-gather reassembles the
  replicated distance vector; convergence is the replicated
  ``any(dist != prev)`` flag (the all-reduce-min analogue: every device
  computes the identical flag from the identical gathered vector).  The
  sparse twin of ``bellman.sssp_bellman_sharded``.

* :func:`sssp_frontier_sharded` — the MPI-message analogue of PR 2's
  frontier engine.  Each sweep every owner compacts its *owned* improved
  vertices and the devices exchange only those ``(global id, dist)`` pairs,
  a fixed-size chunk per all-gather inside a ``lax.while_loop`` whose trip
  count tracks the *largest per-owner frontier* — payload
  O(max_p |frontier_p|) per sweep, not O(n).  Each owner then pushes the
  received frontier through its local source-indexed out-CSR
  (``CsrPartition.out_*``) with the same chunked gather/scatter-min scheme
  as ``core/frontier.py``, so per-sweep relax work is O(arcs from the
  frontier into the owned block) and the psum of the per-owner counters
  equals the single-device engine's ``edges_relaxed`` exactly (each arc
  has one owner).

Distances are bitwise-identical to every other engine: the fixpoint is a
min over the same f32 path sums, and mins are associative/commutative
exactly (same argument as bellman_csr / frontier, covered by
tests/test_sharded_csr.py through n=10000 at P ∈ {1, 2, 4, 8}).

Δ-bucketing is not offered here: the Δ schedule trades sweeps for frontier
width, and the sharded engine's per-sweep cost is already dominated by the
exchange — see core/frontier.py for the single-device Δ variant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core._axes import axis_size, varying
from repro.core.frontier import relax_edge_slots, relax_edge_slots_multi
from repro.obs.metrics import mark_trace

INF = jnp.inf


def partition_operands(parts, mesh: jax.sharding.Mesh,
                       axis: str = "data") -> dict:
    """Stage a core.csr.CsrPartition onto ``mesh`` as the pytree the
    sharded engines consume: each owner's row block goes to the device
    that owns it (``NamedSharding(mesh, P(axis))`` over the leading owner
    axis), so ``shard_map`` finds every block in place instead of
    resharding from one device on each call.  Not memoized, same
    rationale as ``csr_operands``: the host numpy blocks are already
    cached on the CsrGraph, so repeat staging is a plain copy, and caching
    jax buffers on the host container would pin device memory.  Long-lived
    callers that SHOULD pin (serve/registry.py's graph handles) stage once
    and pass the dict back through the engines' ``ops=``."""
    owners = NamedSharding(mesh, P(_axis_key(axis), None))
    return {k: jax.device_put(getattr(parts, k), owners)
            for k in ("in_src", "in_dst_loc", "in_w", "out_indptr",
                      "out_dst_loc", "out_w")}


def sssp_bellman_csr_sharded(
    parts,
    source,
    mesh: jax.sharding.Mesh,
    *,
    axis: str = "data",
    max_sweeps: int | None = None,
    ops: dict | None = None,
):
    """Sharded fixpoint SSSP on a CsrPartition.  Returns
    ``(dist (n_pad,), pred (n_pad,), sweeps, converged)``; valid entries
    ``[:n]``.  ``converged`` (0/1) is the replicated guardrail flag:
    0 iff ``max_sweeps=`` capped the loop before the gathered vector
    stopped changing (labels may sit above their fixpoint — see
    serve/errors.NotConverged).

    Per sweep: local O(m/P) segment-min over the owner's incoming arcs,
    one tiled all-gather of the (loc_n,) block — the same one-collective-
    per-sweep granularity as the dense ``bellman_sharded``, at sparse
    cost.  pred is recovered per owner from its own arcs at the fixpoint
    (same lowest-u tie-break as ``predecessors_from_dist_csr``).
    ``ops=`` accepts an already-staged :func:`partition_operands` dict
    (serve/registry.py pins one per handle) instead of re-staging.
    """
    nprocs = axis_size(mesh, axis)
    assert parts.nprocs == nprocs, (parts.nprocs, nprocs)
    cap = int(parts.n_pad if max_sweeps is None else max_sweeps)
    if ops is None:
        ops = partition_operands(parts, mesh, axis)
    run = _build_bellman(mesh, _axis_key(axis), parts.n_pad, parts.loc_n,
                         cap)
    return run(ops["in_src"], ops["in_dst_loc"], ops["in_w"],
               jnp.asarray(source, jnp.int32))


def _axis_key(axis):
    """Hashable axis argument for the lru_cache'd builders (engines accept
    a name or a tuple of names, like the dense sharded engines)."""
    return axis if isinstance(axis, (str, tuple)) else tuple(axis)


@functools.lru_cache(maxsize=None)
def _build_bellman(mesh, axis, n_pad, loc_n, cap):
    """jit-compiled sharded fixpoint, memoized per (mesh, statics) so
    repeat solves reuse the compiled executable instead of re-tracing the
    shard_map closure every call (same rationale as make_csr_sweep_fn)."""
    nprocs = axis_size(mesh, axis)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None), P()),
        out_specs=(P(axis), P(axis), P(), P()),
    )
    def run(in_src, in_dst_loc, in_w, src):
        mark_trace("bellman_csr_sharded")
        in_src, in_dst_loc, in_w = in_src[0], in_dst_loc[0], in_w[0]
        my_p = lax.axis_index(axis)
        v_base = (my_p * loc_n).astype(jnp.int32)
        dist0 = jnp.full((n_pad,), INF, in_w.dtype).at[src].set(0.0)
        dist0 = varying(dist0, axis)
        prev0 = varying(jnp.full((n_pad,), -1.0, in_w.dtype), axis)

        def seg_min(vals):
            return jax.ops.segment_min(
                vals, in_dst_loc, num_segments=loc_n, indices_are_sorted=True
            )

        def cond(c):
            dist, prev, it = c
            return (it < cap) & jnp.any(dist != prev)

        def body(c):
            dist, _, it = c
            cand = seg_min(dist[in_src] + in_w)          # O(m/P)
            mine = lax.dynamic_slice_in_dim(dist, v_base, loc_n)
            loc_new = jnp.minimum(mine, cand)
            new = lax.all_gather(loc_new, axis, tiled=True)
            return new, dist, it + 1

        it0 = varying(jnp.int32(0), axis)
        dist, prev, sweeps = lax.while_loop(cond, body, (dist0, prev0, it0))
        # every device computes the identical flag from the identical
        # gathered vectors; the psum//nprocs makes replication explicit
        # (same pattern as the sweeps counter below).
        conv = (~jnp.any(dist != prev)).astype(jnp.int32)

        # local pred recovery from the owner's own arcs (sentinel arcs are
        # INF and can only attain on rows whose best is INF, which the
        # reached mask excludes) — matches predecessors_from_dist_csr.
        via = dist[in_src] + in_w
        best = seg_min(via)
        attains = via <= best[in_dst_loc]
        u_cand = jnp.where(attains, in_src, jnp.int32(n_pad))
        u_best = seg_min(u_cand)
        mine = lax.dynamic_slice_in_dim(dist, v_base, loc_n)
        owned = v_base + jnp.arange(loc_n, dtype=jnp.int32)
        reached = jnp.isfinite(mine) & (u_best < n_pad)
        pred = jnp.where(reached & (owned != src), u_best, -1)
        return (mine, pred, lax.psum(sweeps, axis) // nprocs,
                lax.psum(conv, axis) // nprocs)

    return jax.jit(run)


def sssp_frontier_sharded(
    parts,
    source,
    mesh: jax.sharding.Mesh,
    *,
    axis: str = "data",
    max_sweeps: int | None = None,
    exchange_chunk: int = 256,
    relax_chunk: int = 1024,
    ops: dict | None = None,
):
    """Sharded frontier-compacted SSSP on a CsrPartition.  Returns
    ``(dist (n_pad,), sweeps, edges_relaxed, converged)``; valid entries
    ``[:n]``.  ``converged`` (0/1, replicated) is 0 iff ``max_sweeps=``
    stopped the loop while some owner still had an improving frontier —
    the labels may then sit above their fixpoint (serve/errors.
    NotConverged is the serving-layer consumer).
    pred is recovered by the caller at the fixpoint (api.shortest_paths
    reuses the O(m) single-device recovery — the tree is a pure function
    of (dist, graph), so nothing is lost by recovering off-engine).

    Per sweep, each owner ships its improved owned vertices as compacted
    ``(id, dist)`` pairs, ``exchange_chunk`` entries per all-gather; the
    number of exchange rounds is a traced value driven by the largest
    per-owner frontier, so the per-sweep payload is O(max_p |frontier_p|)
    (rounded up to one chunk), not O(n).  Received pairs are pushed
    through the owner's local out-CSR ``relax_chunk`` arc slots at a
    time, the exact scheme of core/frontier.make_flat_sweep_fn.

    ``edges_relaxed`` is the psum over owners of the arcs windowed by the
    received frontier — equal to the single-device frontier engine's
    counter (each arc has exactly one owner; benchmarks/run_bench.py
    gates on this).  ``ops=`` as in :func:`sssp_bellman_csr_sharded`.
    """
    nprocs = axis_size(mesh, axis)
    assert parts.nprocs == nprocs, (parts.nprocs, nprocs)
    cap = int(parts.n_pad if max_sweeps is None else max_sweeps)
    if ops is None:
        ops = partition_operands(parts, mesh, axis)
    run = _build_frontier(mesh, _axis_key(axis), parts.n_pad, parts.loc_n,
                          parts.nnz_max, cap,
                          int(min(exchange_chunk, max(parts.loc_n, 1))),
                          int(relax_chunk))
    return run(ops["out_indptr"], ops["out_dst_loc"], ops["out_w"],
               jnp.asarray(source, jnp.int32))


@functools.lru_cache(maxsize=None)
def _build_frontier(mesh, axis, n_pad, loc_n, nnz_max, cap, CH, RC):
    """jit-compiled sharded frontier engine, memoized like _build_bellman."""
    nprocs = axis_size(mesh, axis)
    fcap = -(-loc_n // CH) * CH                  # frontier buffer, CH-aligned

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None), P()),
        out_specs=(P(axis), P(), P(), P()),
    )
    def run(out_indptr, out_dst_loc, out_w, src):
        mark_trace("frontier_sharded")
        out_indptr, out_dst_loc, out_w = (
            out_indptr[0], out_dst_loc[0], out_w[0])
        my_p = lax.axis_index(axis)
        v_base = (my_p * loc_n).astype(jnp.int32)
        owned = v_base + jnp.arange(loc_n, dtype=jnp.int32)
        dist0 = jnp.where(owned == src, 0.0, INF).astype(out_w.dtype)
        fmask0 = owned == src

        def relax(nd, all_ids, all_ds, edges):
            """Push one gathered frontier chunk through the local out-CSR
            with the same chunked slot-relax as the single-device engine
            (core/frontier.relax_edge_slots) — source distances come from
            the exchanged pairs, targets are block-local ids."""
            starts = out_indptr[all_ids]
            degs = out_indptr[all_ids + 1] - starts
            csum = jnp.cumsum(degs)
            E, off = csum[-1], csum - degs
            nd = relax_edge_slots(
                nd, all_ds, starts, off, E, out_dst_loc, out_w,
                chunk=RC, drop_id=jnp.int32(loc_n),
            )
            return nd, edges + E

        def cond(c):
            _, _, it, _, go = c
            return (it < cap) & go

        def body(c):
            dist, fmask, it, edges, _ = c
            # compact this owner's frontier: (global id, snapshot dist),
            # sentinel (n_pad, INF) — zero out-degree via the extra row.
            fidx = jnp.nonzero(fmask, size=fcap, fill_value=loc_n)[0]
            fidx = fidx.astype(jnp.int32)
            live = fidx < loc_n
            gid = jnp.where(live, v_base + fidx, jnp.int32(n_pad))
            fd = jnp.where(live, dist[jnp.minimum(fidx, loc_n - 1)], INF)
            max_cnt = lax.pmax(jnp.sum(fmask), axis)

            def ex_cond(c2):
                return c2[2] * CH < max_cnt

            def ex_body(c2):
                nd, e, k = c2
                ids = lax.dynamic_slice_in_dim(gid, k * CH, CH)
                ds = lax.dynamic_slice_in_dim(fd, k * CH, CH)
                all_ids = lax.all_gather(ids, axis, tiled=True)  # (P*CH,)
                all_ds = lax.all_gather(ds, axis, tiled=True)
                nd, e = relax(nd, all_ids, all_ds, e)
                return nd, e, k + 1

            nd, edges, _ = lax.while_loop(
                ex_cond, ex_body, (dist, edges, jnp.int32(0)))
            improved = nd < dist
            go = lax.psum(jnp.any(improved).astype(jnp.int32), axis) > 0
            return nd, improved, it + 1, edges, go

        it0 = varying(jnp.int32(0), axis)
        e0 = varying(jnp.int32(0), axis)
        go0 = jnp.bool_(True)       # invariant: the body returns a psum
        dist, _, sweeps, edges, go = lax.while_loop(
            cond, body, (dist0, fmask0, it0, e0, go0))
        # go is the psummed work-remains flag (replicated): exiting with
        # it still set means the cap fired mid-convergence.
        conv = (~go).astype(jnp.int32)
        return (dist, lax.psum(sweeps, axis) // nprocs,
                lax.psum(edges, axis), lax.psum(conv, axis) // nprocs)

    return jax.jit(run)


def sssp_multisource_csr_sharded(
    parts,
    sources,
    mesh: jax.sharding.Mesh,
    *,
    axis: str = "data",
    max_sweeps: int | None = None,
    exchange_chunk: int = 256,
    relax_chunk: int = 1024,
    ops: dict | None = None,
):
    """Batched vertex-partitioned SSSP from S sources on a CsrPartition —
    the multisource coalescing of :func:`sssp_frontier_sharded`.  Returns
    ``(D (S, n_pad), sweeps, edges_relaxed, converged)``; valid columns
    ``[:n]``.  ``converged`` (0/1, replicated) is the joint guardrail
    flag over all S rows, same contract as the other sharded engines.

    Per sweep each owner compacts the UNION over sources of its owned
    improved vertices and the devices exchange ``(global id, per-source
    dist column)`` pairs — the id chunk is the same payload as the
    single-source engine, the distance chunk grows to (S, CH).  Each
    received frontier vertex's out-arc window is then gathered ONCE and
    relaxed against all S source rows (core/frontier.
    relax_edge_slots_multi), so the edge-index loads are amortized S ways
    on top of the P-way partitioning — Kainer & Träff's many-settled-
    vertices-per-round observation (arXiv:1903.12085) applied across the
    batch axis.

    ``edges_relaxed`` counts each windowed arc ONCE per sweep however
    many sources share the gather (psummed over owners) — directly
    comparable to S single-source ``frontier`` solves, whose counters
    sum the same windows per source; whenever two batched sources'
    frontiers overlap in a sweep the union counter is strictly smaller
    (benchmarks/serve_bench.py's sharded gate measures exactly this).

    Per-source rows are bitwise-equal to S independent solves of any
    engine: the union frontier is a superset of every per-source
    frontier, so no per-source improvement is ever missed, and the
    fixpoint is the same min over the same f32 path sums.  pred is not
    recovered (same contract as ``multisource_csr``; api.recover_pred
    rebuilds rows on demand).  ``ops=`` as in the other engines here.
    """
    nprocs = axis_size(mesh, axis)
    assert parts.nprocs == nprocs, (parts.nprocs, nprocs)
    cap = int(parts.n_pad if max_sweeps is None else max_sweeps)
    if ops is None:
        ops = partition_operands(parts, mesh, axis)
    srcs = jnp.atleast_1d(jnp.asarray(sources, jnp.int32))
    run = _build_multisource_frontier(
        mesh, _axis_key(axis), parts.n_pad, parts.loc_n, cap,
        int(min(exchange_chunk, max(parts.loc_n, 1))), int(relax_chunk),
        int(srcs.shape[0]))
    return run(ops["out_indptr"], ops["out_dst_loc"], ops["out_w"], srcs)


@functools.lru_cache(maxsize=None)
def _build_multisource_frontier(mesh, axis, n_pad, loc_n, cap, CH, RC, S):
    """jit-compiled sharded multisource union-frontier engine, memoized
    per (mesh, statics, S) — serving buckets the source axis to powers of
    two (serve/scheduler.py), so the cache stays small."""
    nprocs = axis_size(mesh, axis)
    fcap = -(-loc_n // CH) * CH                  # frontier buffer, CH-aligned

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None), P()),
        out_specs=(P(None, axis), P(), P(), P()),
    )
    def run(out_indptr, out_dst_loc, out_w, srcs):
        mark_trace("multisource_csr_sharded")
        out_indptr, out_dst_loc, out_w = (
            out_indptr[0], out_dst_loc[0], out_w[0])
        my_p = lax.axis_index(axis)
        v_base = (my_p * loc_n).astype(jnp.int32)
        owned = v_base + jnp.arange(loc_n, dtype=jnp.int32)
        is_src = owned[None, :] == srcs[:, None]          # (S, loc_n)
        D0 = jnp.where(is_src, 0.0, INF).astype(out_w.dtype)
        fmask0 = jnp.any(is_src, axis=0)

        def relax(ND, all_ids, all_D, edges):
            """Push one gathered union-frontier chunk through the local
            out-CSR: window arithmetic and arc gathers once per slot,
            candidates per source (relax_edge_slots_multi)."""
            starts = out_indptr[all_ids]
            degs = out_indptr[all_ids + 1] - starts
            csum = jnp.cumsum(degs)
            E, off = csum[-1], csum - degs
            ND = relax_edge_slots_multi(
                ND, all_D, starts, off, E, out_dst_loc, out_w,
                chunk=RC, drop_id=jnp.int32(loc_n),
            )
            return ND, edges + E

        def cond(c):
            _, _, it, _, go = c
            return (it < cap) & go

        def body(c):
            D, fmask, it, edges, _ = c
            # compact the union frontier; every live pair ships its FULL
            # per-source distance column — a vertex improved for one
            # source re-pushes its (already-applied) labels for the
            # others, inert under min.
            fidx = jnp.nonzero(fmask, size=fcap, fill_value=loc_n)[0]
            fidx = fidx.astype(jnp.int32)
            live = fidx < loc_n
            gid = jnp.where(live, v_base + fidx, jnp.int32(n_pad))
            fdm = jnp.where(live[None, :],
                            D[:, jnp.minimum(fidx, loc_n - 1)], INF)
            max_cnt = lax.pmax(jnp.sum(fmask), axis)

            def ex_cond(c2):
                return c2[2] * CH < max_cnt

            def ex_body(c2):
                ND, e, k = c2
                ids = lax.dynamic_slice_in_dim(gid, k * CH, CH)
                ds = lax.dynamic_slice_in_dim(fdm, k * CH, CH, axis=1)
                all_ids = lax.all_gather(ids, axis, tiled=True)  # (P*CH,)
                all_D = lax.all_gather(ds, axis, axis=1, tiled=True)
                ND, e = relax(ND, all_ids, all_D, e)
                return ND, e, k + 1

            ND, edges, _ = lax.while_loop(
                ex_cond, ex_body, (D, edges, jnp.int32(0)))
            improved = jnp.any(ND < D, axis=0)
            go = lax.psum(jnp.any(improved).astype(jnp.int32), axis) > 0
            return ND, improved, it + 1, edges, go

        it0 = varying(jnp.int32(0), axis)
        e0 = varying(jnp.int32(0), axis)
        go0 = jnp.bool_(True)       # invariant: the body returns a psum
        D, _, sweeps, edges, go = lax.while_loop(
            cond, body, (D0, fmask0, it0, e0, go0))
        conv = (~go).astype(jnp.int32)
        return (D, lax.psum(sweeps, axis) // nprocs,
                lax.psum(edges, axis), lax.psum(conv, axis) // nprocs)

    return jax.jit(run)
