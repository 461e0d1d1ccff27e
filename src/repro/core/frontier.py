"""Frontier-compacted SSSP over outgoing CSR edges — O(frontier out-degree)
per sweep.

The paper's §V diagnosis (inherited verbatim by ``bellman_csr``): the
fixpoint relaxes *every* edge every sweep, so sweeps late in convergence do
O(m) work to improve a handful of vertices.  Δ-stepping (Kranjčević et al.,
arXiv:1604.02113) and Kainer & Träff (arXiv:1903.12085) both locate the win
in restricting relaxation to the **active frontier** — the vertices whose
distance improved last sweep.  This engine does exactly that, with every
shape static so the whole loop stays inside one jit:

1. **Compact** the frontier mask with a static-size ``jnp.nonzero`` (padded
   with the sentinel id n) and an exclusive cumsum of out-degrees — the
   classic stream-compaction step of GPU frontier BFS/SSSP.
2. **Gather** only the frontier vertices' out-edge windows from the
   outgoing CSR view (``CsrGraph.out_csr()``), up to a chunk of edge slots
   at a time in an inner ``lax.while_loop`` whose trip count is traced, so
   per-sweep work tracks the actual frontier edge count E (rounded up to
   one chunk) instead of m.  The rows a chunk touches are one contiguous
   run of the compaction, so the loop carries a row cursor and finds each
   slot's row by comparing it with one ``chunk + 1`` slice of the window
   offsets taken at the cursor, with no per-slot search over the whole
   frontier.  A run of rows with no slots can cut a step short, and the
   cursor still moves a chunk of rows on: ``ceil(E / chunk)`` steps when
   every row has a slot.  Each slot still meets the same row and arc, so
   the candidates, and the fixpoint, are bitwise unchanged.
3. **Scatter-min** the candidates ``dist[u] + w`` into the new distance
   vector with ``.at[dst].min`` — the TPU-legal replacement for the CUDA
   kernel's ``atomicMin``, associative and deterministic.

Per-sweep results are bitwise identical to ``bellman_csr`` restricted to
the frontier's candidate set, and the fixpoint (hence the distances) is
bitwise identical to every other engine: min over the same f32 path sums.

An optional **Δ-bucket schedule** (``delta=...``) bounds frontier growth on
weighted graphs: only pending vertices with ``dist <= limit`` are expanded,
and the limit advances by Δ when the current bucket drains — Δ-stepping
restricted to the jit-static state (dist, pending, limit).  ``delta=None``
(default) expands the full improved set each sweep (Bellman-Ford ordering).
The TRUE Δ-stepping engine — light/heavy edge split, per-bucket light
fixpoint, one heavy pass per settled bucket — lives in
core/delta_stepping.py and reuses this module's compaction machinery
(:func:`relax_active`, :func:`make_flat_sweep_fn`, :func:`sweep_cap`).

An optional **target early exit** (``target=...``) stops the fixpoint as
soon as ``dist[target]`` is provably final: with nonnegative weights any
future improvement to the target must route through a pending vertex ``u``
with ``dist[u] < dist[target]``, so once every pending label is >=
``dist[target]`` no relaxation sequence can lower it — the Dijkstra
settled-vertex argument applied to the whole pending set.  The returned
``dist[target]`` is bitwise identical to the full solve's; other entries
may still be above their fixpoint (only vertices with ``dist <
dist[target]`` are guaranteed settled).  ``target_lb=`` sharpens the rule
with an admissible lower bound (e.g. an ALT landmark bound, see
serve/landmarks.py): the loop also stops when ``dist[target] <=
target_lb``, exact because a label can only equal the true distance once
it is <= any admissible bound.  An inadmissible (too large) bound would
break exactness; a too-small bound merely never fires.

The engine also counts **edges relaxed** (sum of frontier out-degrees over
all sweeps) so the O(frontier) claim is measurable: ``bellman_csr`` relaxes
``nnz * sweeps``; this engine's counter is strictly smaller whenever any
sweep's frontier misses a vertex (see benchmarks/run_bench.py's gate).

The kernel path (api engine ``frontier_kernel``) swaps the inner chunk
relax for the Pallas candidate kernel in kernels/frontier_relax, which
streams the compacted frontier's padded out-ELL windows (CsrGraph.out_ell)
in fixed-size row blocks.

The stages carry stable ``jax.named_scope`` names, which a profiler trace
keeps in each device op's ``tf_op`` path: ``frontier.compact`` (step 1,
and the gather of the frontier rows' distances), ``frontier.relax`` (the
edge-slot walk of steps 2–3) and ``frontier.test`` (the stopping rule and
the pending-set update).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.bellman_csr import csr_operands, predecessors_from_dist_csr
from repro.obs.metrics import mark_trace

INF = jnp.inf


def frontier_operands(cg, *, with_ell: bool = False,
                      base_ops: Optional[dict] = None) -> dict:
    """Stage a core.csr.CsrGraph for the frontier engine.

    Extends :func:`csr_operands` (incoming src/dst/w — kept for the O(m)
    pred recovery at the fixpoint) with the outgoing CSR view.  The
    out-indptr is staged with one extra trailing entry so the compaction
    sentinel id n indexes a zero-degree row instead of falling off the end.
    ``with_ell`` adds the padded out-ELL view the Pallas kernel consumes.
    ``base_ops`` reuses already-staged :func:`csr_operands` arrays instead
    of uploading src/dst/w again (serve/registry.py holds both views on
    one long-lived handle and must not double-stage the O(m) arrays).
    """
    ops = dict(base_ops) if base_ops is not None else csr_operands(cg)
    indptr, out_dst, out_w = cg.out_csr()
    indptr_s = np.concatenate([indptr, indptr[-1:]])     # (n + 2,)
    ops["out_indptr"] = jnp.asarray(indptr_s, jnp.int32)
    ops["out_dst"] = jnp.asarray(out_dst)
    ops["out_w"] = jnp.asarray(out_w)
    if with_ell:
        ell_idx, ell_w = cg.out_ell()
        ops["out_ell_idx"] = jnp.asarray(ell_idx)
        ops["out_ell_w"] = jnp.asarray(ell_w)
    return ops


def _slot_minloop(nd, starts, off, E, m, F, *, chunk: int, emit,
                  scatter=None):
    """Chunked slot walker shared by the push and pull relax forms: walk
    ``E`` edge slots at most ``chunk`` at a time in a ``lax.while_loop``
    (trip count tracks the actual slot count, the stream-compaction core
    of the frontier engines), map each slot to its owning compacted row —
    the last row whose window starts at or before the slot, landing past
    zero-degree ties — and its in-window position, then scatter-min
    whatever ``emit(row, pos, valid) -> (cand, tgt)`` produces (invalid
    slots must emit INF aimed at a drop id; scatter mode="drop").
    ``scatter`` overrides the per-slot scatter-min for callers whose state
    isn't a flat (n,) row — the multisource form scatter-mins a (S, chunk)
    candidate block into distance-matrix columns.

    The rows a step's slots belong to are one contiguous run of the
    compaction, so the loop carries a **row cursor** ``r0`` (at or before
    the owner of the step's first slot ``b``) and reads one contiguous
    ``chunk + 1`` slice of ``off`` from it; ``off`` is padded once per
    walk past its end with the dtype's maximum, so the slice never
    clamps.  A slot's row is ``r0 + #(slice <= slot) - 1``, a dense
    compare-and-sum with no gather.  The step's slots stop at
    ``min(b + chunk, off[r0 + chunk], E)`` (never below ``b``), the
    ``+1`` entry bounding the slots whose owner lies inside the slice.
    When every row has a slot that bound never bites; a run of zero-slot
    rows makes a short (even empty) step, which still moves the cursor
    ``chunk`` rows on, so the walk takes at most ``ceil(E / chunk) +
    ceil(Z / chunk)`` steps with Z zero-slot rows.  Every slot in
    ``[0, E)`` is emitted exactly once with the same row and position as a
    whole-array search would give, and scatter-min is exact and
    order-free, so the result is bitwise the same however the slots fall
    into steps.

    Returns ``(nd, steps)``, the walk's step count."""
    if scatter is None:
        def scatter(nd2, tgt, cand):
            return nd2.at[tgt].min(cand, mode="drop")

    lane = jnp.arange(chunk, dtype=off.dtype)
    offp = jnp.concatenate(
        [off, jnp.full((chunk + 1,), jnp.iinfo(off.dtype).max, off.dtype)])

    def cond(carry):
        _, b, _, _ = carry
        return b < E

    def body(carry):
        nd2, b, r0, steps = carry
        win = lax.dynamic_slice_in_dim(offp, r0, chunk + 1)
        limit = jnp.clip(jnp.minimum(b + chunk, win[-1]), b, E)
        slots = b + lane
        valid = slots < limit
        row = r0 + jnp.sum(win[:, None] <= slots[None, :], axis=0,
                           dtype=r0.dtype) - 1
        row = jnp.clip(row, 0, F - 1)
        pos = starts[row] + (slots - off[row])
        pos = jnp.clip(pos, 0, m - 1)
        cand, tgt = emit(row, pos, valid)
        r0 = r0 + jnp.sum(win <= limit, dtype=r0.dtype) - 1
        return scatter(nd2, tgt, cand), limit, r0, steps + 1

    zero = jnp.zeros_like(E, off.dtype)     # varies wherever E does
    with jax.named_scope("frontier.relax"):
        nd, _, _, steps = lax.while_loop(
            cond, body, (nd, zero, zero, jnp.int32(0)))
    return nd, steps


def relax_edge_slots(nd, row_dist, starts, off, E, out_dst, out_w, *,
                     chunk: int, drop_id):
    """Scatter-min ``row_dist[row] + w`` over a compacted frontier's edge
    slots (the PUSH form of :func:`_slot_minloop`).

    Shared by the single-device flat sweep (:func:`make_flat_sweep_fn`)
    and the vertex-partitioned local relax (core/sharded_csr.py) — the
    callers differ only in where the source distances come from (the
    local ``dist`` snapshot vs the exchanged frontier pairs) and in the
    scatter target space (global ids dropped at n vs block-local ids
    dropped at loc_n, via ``drop_id``).

    row_dist: (F,) source distance per frontier row; starts/off: each
    row's window start in (out_dst, out_w) / exclusive cumsum of window
    lengths; E: total slots; out-of-window slots produce INF candidates
    aimed at ``drop_id``.
    """
    m = out_dst.shape[0]
    if m == 0:                                    # edgeless graph: no work
        return nd

    def emit(row, pos, valid):
        cand = jnp.where(valid, row_dist[row] + out_w[pos], INF)
        tgt = jnp.where(valid, out_dst[pos], drop_id)
        return cand, tgt

    return _slot_minloop(nd, starts, off, E, m, row_dist.shape[0],
                         chunk=chunk, emit=emit)[0]


def relax_edge_slots_multi(ND, row_D, starts, off, E, out_dst, out_w, *,
                           chunk: int, drop_id):
    """Multisource PUSH form of :func:`_slot_minloop`: scatter-min
    ``row_D[:, row] + w`` into ``ND[:, dst]`` for every source at once.

    The multisource coalescing of :func:`relax_edge_slots`: the edge-slot
    walk — window arithmetic, out_dst/out_w gathers — runs ONCE per slot
    chunk and is shared by all S sources; only the candidate block is
    per-source ((S, chunk), one gathered edge weight broadcast across the
    source axis).  Used by the vertex-partitioned batched engine
    (core/sharded_csr.sssp_multisource_csr_sharded), where the compacted
    frontier is the UNION over sources of last sweep's improved vertices.

    ND: (S, n') distance matrix; row_D: (S, F) per-source distances of the
    compacted frontier rows; remaining args as in :func:`relax_edge_slots`.
    """
    m = out_dst.shape[0]
    if m == 0:                                    # edgeless graph: no work
        return ND

    def emit(row, pos, valid):
        cand = jnp.where(valid[None, :], row_D[:, row] + out_w[pos][None, :],
                         INF)
        tgt = jnp.where(valid, out_dst[pos], drop_id)
        return cand, tgt

    def scatter(nd2, tgt, cand):
        return nd2.at[:, tgt].min(cand, mode="drop")

    return _slot_minloop(ND, starts, off, E, m, row_D.shape[1],
                         chunk=chunk, emit=emit, scatter=scatter)[0]


def pull_edge_slots(nd, fids, src_dist, starts, off, E, in_src, in_w, *,
                    chunk: int, drop_id):
    """The PULL form of :func:`_slot_minloop`: scatter-min
    ``src_dist[in_src[pos]] + in_w[pos]`` into each compacted row's OWN
    vertex.

    Where the push form relaxes a frontier row's *outgoing* window toward
    per-slot destinations, this relaxes a row's *incoming* window toward
    the row itself — ``fids[row]`` is the scatter target and the source
    distance is gathered per slot.  dynamic/repair.py uses it to re-derive
    the invalidated cone's labels from its boundary in O(cone in-degree):
    the compacted rows are the affected vertices, the windows come from
    the incoming CSR, and non-boundary sources carry INF so only live
    support contributes.  Sentinel rows (``fids == drop_id``) scatter to
    ``drop_id`` and are dropped.
    """
    m = in_src.shape[0]
    if m == 0:
        return nd

    def emit(row, pos, valid):
        cand = jnp.where(valid, src_dist[in_src[pos]] + in_w[pos], INF)
        tgt = jnp.where(valid, fids[row], drop_id)
        return cand, tgt

    return _slot_minloop(nd, starts, off, E, m, fids.shape[0],
                         chunk=chunk, emit=emit)[0]


@functools.lru_cache(maxsize=None)
def make_flat_sweep_fn(chunk: int = 1024) -> Callable:
    """Default frontier sweep: flat-CSR edge windows, ``chunk`` edge slots
    per inner step.  Memoized so the closure identity is stable — it is a
    static jit argument of the engine (same contract as make_csr_sweep_fn).

    The sweep contract (shared with kernels/frontier_relax/ops.py):
    ``sweep(dist, fids, starts, off, E, fcount, ops) -> new_dist`` where
    fids (n,) are the compacted frontier ids (sentinel-n padded), starts
    their out-window starts, off the exclusive cumsum of their out-degrees,
    E the total frontier out-degree and fcount the frontier size.  Reads
    come from the ``dist`` snapshot (Jacobi sweep semantics, like every
    other engine), writes scatter-min into the running copy.
    """

    def sweep(dist, fids, starts, off, E, fcount, ops):
        # trace-time marker: the sweep body re-executes only when some
        # enclosing engine retraces (shape/static drift) — the counter
        # tests/test_obs.py pins at zero across repeat ticks/versions
        mark_trace("flat_sweep")
        n = dist.shape[0]
        with jax.named_scope("frontier.compact"):
            # sentinel rows: 0 slots
            row_dist = dist[jnp.minimum(fids, n - 1)]
        return relax_edge_slots(
            dist, row_dist, starts, off, E, ops["out_dst"], ops["out_w"],
            chunk=chunk, drop_id=jnp.int32(n),
        )

    return sweep


def relax_active(ops: dict, dist, active, *, n: int, sweep: Callable):
    """Compact the ``active`` mask and relax its out-edge windows once —
    the stream-compaction + sweep core shared by :func:`frontier_fixpoint`
    and the Δ-stepping heavy phase (core/delta_stepping.py), so the two
    schedules cannot drift in compaction or window arithmetic.

    ``ops`` needs the sweep contract's keys (out_indptr staged with the
    trailing sentinel row, out_dst, out_w — see :func:`frontier_operands`;
    the Δ engine passes an aliased view of its heavy split).  Must be
    called inside jit.  Returns ``(new_dist, E)`` with E the total
    out-degree of the active set (the edges-relaxed increment).
    """
    with jax.named_scope("frontier.compact"):
        fids = jnp.nonzero(active, size=n, fill_value=n)[0].astype(jnp.int32)
        fcount = jnp.sum(active)
        starts = ops["out_indptr"][fids]
        degs = ops["out_indptr"][fids + 1] - starts
        csum = jnp.cumsum(degs)
        E, off = csum[-1], csum - degs
    new = sweep(dist, fids, starts, off, E, fcount, ops)
    return new, E


def sweep_cap(n: int, delta: float | None, max_sweeps: int | None,
              max_dist=None):
    """Fixpoint sweep bound shared by every frontier-family engine
    (sssp_frontier here, sssp_frontier_dynamic / sssp_repair in
    dynamic/repair.py, and the Δ-stepping engine's outer-phase cap): the
    hop-diameter bound n for the plain schedule; headroom under
    Δ-bucketing, whose deferred vertices re-enter later buckets.  The
    pending-empty exit is the real stop — the cap is a divergence guard.

    With ``max_dist`` (an upper bound on the largest finite distance,
    e.g. (n-1)·w_max from the staged weights) the Δ headroom is derived
    instead of guessed: the bucket limit only ever advances past the
    current minimum pending label, so it advances at most
    ``ceil(max_dist / Δ) + 1`` times before clearing every finite label;
    every other sweep relaxes a nonempty active set containing the
    minimum pending vertex, whose label is final (the Dijkstra argument),
    so at most n such sweeps exist.  Hence
    ``cap = n + ceil(max_dist / Δ) + 1``, with the legacy ``4·n``
    constant kept as a floor for callers whose bound is loose or traced.
    ``max_dist`` may be a traced scalar — the result is then traced too
    (fine as a ``lax.while_loop`` bound); without it the legacy static
    ``4·n`` is returned unchanged.
    """
    if max_sweeps is not None:
        return max_sweeps
    if delta is None:
        return n
    if max_dist is None:
        return 4 * n
    buckets = jnp.ceil(jnp.asarray(max_dist, jnp.float32)
                       / jnp.float32(delta)) + 1.0
    # non-finite or huge bounds (disconnected staging, f32 overflow) would
    # wrap int32: clamp the bucket count, the floor still applies.
    buckets = jnp.where(jnp.isfinite(buckets), buckets, 2.0 ** 30)
    buckets = jnp.clip(buckets, 0.0, 2.0 ** 30).astype(jnp.int32)
    return jnp.maximum(jnp.int32(4 * n), jnp.int32(n) + buckets)


def frontier_fixpoint(
    ops: dict,
    dist0,
    pending0,
    *,
    n: int,
    sweep: Callable,
    cap: int,
    delta: float | None = None,
    target=None,
    target_lb=None,
    edges0=0,
):
    """The frontier relax loop on an ARBITRARY initial state — factored out
    of :func:`sssp_frontier` so callers with a warm start can reuse the
    exact machinery (compaction, Δ-bucket schedule, target early exit,
    edge counter).  dynamic/repair.py seeds it with a mutated graph's
    partially-invalidated distance vector instead of a cold source.

    Correctness contract for a warm start: ``dist0`` must be pointwise >=
    the true fixpoint with ``dist0[source] == 0``, every finite label must
    be a real path length in the graph ``ops`` describes, and ``pending0``
    must cover every vertex whose label has improved relative to what its
    out-neighbors last saw — the loop then converges to the same fixpoint
    a cold solve reaches, bitwise (min over the same f32 path sums).

    Must be called inside jit (trace-time only).  Returns
    ``(dist, sweeps, edges_relaxed, converged)`` with ``edges_relaxed``
    accumulated on top of ``edges0`` and ``converged`` True iff the loop
    exited because the fixpoint (or the target's settled condition) was
    reached rather than because the sweep ``cap`` ran out — the solver
    guardrail serve/errors.NotConverged consumes.
    """
    limit0 = jnp.float32(0.0 if delta is None else delta)

    @jax.named_scope("frontier.test")
    def settled_or_done(dist, pending):
        done = ~jnp.any(pending)
        if target is not None:
            dt = dist[target]
            # settled once no pending label is below the target's: every
            # future candidate is dist[u] + w >= dist[u] >= min pending.
            settled = jnp.min(jnp.where(pending, dist, INF)) >= dt
            if target_lb is not None:
                # an admissible bound pins the label from below; label >=
                # true distance always, so equality at the bound is final.
                settled = settled | (dt <= target_lb)
            done = done | settled
        return done

    def cond(carry):
        dist, pending, _, it, _ = carry
        return (it < cap) & ~settled_or_done(dist, pending)

    def body(carry):
        dist, pending, limit, it, edges = carry
        if delta is None:
            active = pending
        else:
            has = jnp.any(pending & (dist <= limit))
            nxt = jnp.min(jnp.where(pending, dist, INF)) + delta
            limit = jnp.where(has, limit, nxt)
            active = pending & (dist <= limit)
        new, E = relax_active(ops, dist, active, n=n, sweep=sweep)
        with jax.named_scope("frontier.test"):
            improved = new < dist
            pending = (pending & ~active) | improved
        return new, pending, limit, it + 1, edges + E

    dist, pending, _, sweeps, edges = lax.while_loop(
        cond, body,
        (dist0, pending0, limit0, jnp.int32(0), jnp.int32(edges0)),
    )
    return dist, sweeps, edges, settled_or_done(dist, pending)


@functools.partial(
    jax.jit, static_argnames=("n", "sweep_fn", "max_sweeps", "delta", "chunk")
)
def sssp_frontier(
    ops: dict,
    source: jax.Array,
    *,
    n: int,
    sweep_fn: Optional[Callable] = None,
    max_sweeps: int | None = None,
    delta: float | None = None,
    chunk: int = 1024,
    target: Optional[jax.Array] = None,
    target_lb: Optional[jax.Array] = None,
):
    """Frontier-compacted fixpoint SSSP on :func:`frontier_operands`.

    Returns ``(dist, pred, num_sweeps, edges_relaxed, converged)`` —
    ``edges_relaxed`` being the total frontier out-degree summed over
    sweeps, the engine's actual relaxation work (compare ``nnz *
    num_sweeps`` for ``bellman_csr``), and ``converged`` the guardrail
    flag: False iff ``max_sweeps=`` stopped the loop before the pending
    set drained (or, for target solves, before the target settled) — the
    labels may then sit above their fixpoint and must not be served as
    exact (serve/errors.NotConverged).

    ``delta`` enables the Δ-bucket schedule (see module docstring): when a
    bucket drains, the same sweep advances the limit and immediately
    relaxes the next bucket's active set, so every sweep does edge work —
    but deferred vertices re-enter later buckets, which can take more
    sweeps than the plain schedule.  ``chunk`` sizes the inner edge-slot
    blocks of the default sweep (ignored when ``sweep_fn`` is given).

    ``target`` enables the early-exit stopping rule (module docstring):
    the loop also stops once ``min(dist[pending]) >= dist[target]`` — or,
    with an admissible ``target_lb``, once ``dist[target] <= target_lb``.
    ``dist[target]`` (and every vertex with a smaller label) is then final
    and bitwise-equal to the full solve; labels above it may be partial,
    so the returned ``pred`` is None (recovering a part-invalid tree
    would cost a full O(m) pass every target caller discards).
    """
    mark_trace("frontier")
    sweep = sweep_fn or make_flat_sweep_fn(chunk)
    cap = sweep_cap(n, delta, max_sweeps)
    dist0 = jnp.full((n,), INF, ops["out_w"].dtype).at[source].set(0.0)
    pending0 = dist0 < INF
    dist, sweeps, edges, converged = frontier_fixpoint(
        ops, dist0, pending0, n=n, sweep=sweep, cap=cap, delta=delta,
        target=target, target_lb=target_lb,
    )
    if target is not None:
        # a target= solve is partial: labels above dist[target] may sit
        # off their fixpoint, so the O(m) recovery would produce a
        # part-invalid tree every caller discards anyway — skip it
        # (trace-time branch: target's presence already keys the trace).
        return dist, None, sweeps, edges, converged
    pred = predecessors_from_dist_csr(dist, ops, source)
    return dist, pred, sweeps, edges, converged
