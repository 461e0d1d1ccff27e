"""Dynamic-graph subsystem correctness: overlays, incremental repair, and
the serving integration.

The load-bearing invariant mirrors the serving suite's: whatever path a
distance takes through the dynamic machinery — overlay full solve,
incremental repair (insert / delete / weight increase / decrease,
including disconnection and reconnection), repaired-in-place cache row,
lazily refreshed landmark — it is **bitwise-equal to a fresh ``serial``
solve on the mutated snapshot**.  Plus the machinery itself: overlay
semantics and versioning, compaction, static-shape jit-cache stability,
pull_edge_slots against a naive reference, cone sublinearity, the
scheduler's mutation ticks with selective invalidation/repair, churn
traces, and the registry-eviction-purges-every-version interplay.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from conftest import dijkstra_oracle
from repro.core import csr as C
from repro.core.api import shortest_paths
from repro.core.bellman_csr import sssp_bellman_csr, sssp_multisource_csr
from repro.core.frontier import pull_edge_slots, sssp_frontier
from repro.dynamic import (DynamicGraph, dynamic_segment_sweep,
                           dynamic_segment_sweep_multi,
                           make_dynamic_flat_sweep_fn, repair_sssp,
                           row_affected, solve_dynamic)
from repro.serve import (DistanceCache, GraphRegistry, MicroBatchScheduler,
                         MutationEvent, TraceEvent, make_churn_trace)


def _serial(dyn_or_cg, s):
    cg = (dyn_or_cg.snapshot() if isinstance(dyn_or_cg, DynamicGraph)
          else dyn_or_cg)
    return shortest_paths(cg, s, engine="serial")


def _mixed_edits(dyn, rng, count):
    """Apply ``count`` seeded mixed edits (add/delete/update) to dyn."""
    applied = 0
    while applied < count:
        u, v = int(rng.integers(dyn.n)), int(rng.integers(dyn.n))
        if u == v:
            continue
        if dyn.has_edge(u, v):
            if rng.random() < 0.45:
                dyn.delete_edge(u, v)
            else:
                dyn.update_edge(u, v, float(rng.uniform(0.5, 100)))
        else:
            dyn.add_edge(u, v, float(rng.uniform(0.5, 100)))
        applied += 1


# ---------------------------------------------------------------------------
# overlay semantics
# ---------------------------------------------------------------------------

def test_overlay_mutation_semantics_and_snapshot():
    cg = C.random_csr_graph(80, 240, seed=0)
    dyn = DynamicGraph(cg, overlay_capacity=8)
    # independent mirror of the edge set
    u = np.asarray(cg.indices, np.int64)
    v = cg.dst_ids().astype(np.int64)
    mirror = {(int(a), int(b)): float(w)
              for a, b, w in zip(u, v, cg.weights) if a < b}

    def set_mirror(a, b, w):
        key = (min(a, b), max(a, b))
        if w is None:
            del mirror[key]
        else:
            mirror[key] = np.float32(w)

    dyn.add_edge(0, 79, 3.25);  set_mirror(0, 79, 3.25)
    some = next(iter(mirror))
    dyn.update_edge(some[1], some[0], 42.0);  set_mirror(*some, 42.0)
    gone = next(k for k in mirror if k != some)
    dyn.delete_edge(*gone);  set_mirror(*gone, None)
    batch = dyn.commit()
    assert dyn.version == 1 and len(batch) == 3
    # snapshot == independently built CSR of the mirror
    e = np.array(sorted(mirror), np.int64)
    w = np.array([mirror[tuple(k)] for k in sorted(mirror)], np.float32)
    want = C.csr_from_edge_list(80, e, w)
    snap = dyn.snapshot()
    assert np.array_equal(snap.indptr, want.indptr)
    assert np.array_equal(snap.indices, want.indices)
    assert np.array_equal(snap.weights, want.weights)
    # undirected: both arcs visible through weight_of
    assert dyn.weight_of(79, 0) == np.float32(3.25)
    assert not dyn.has_edge(*gone)


def test_overlay_rejects_invalid_mutations():
    cg = C.random_csr_graph(20, 60, seed=1)
    dyn = DynamicGraph(cg)
    live = (int(cg.indices[0]), int(cg.dst_ids()[0]))
    absent = next((a, b) for a in range(20) for b in range(a + 1, 20)
                  if not dyn.has_edge(a, b))
    with pytest.raises(ValueError, match="already present"):
        dyn.add_edge(*live, 1.0)
    with pytest.raises(ValueError, match="not present"):
        dyn.update_edge(*absent, 1.0)
    with pytest.raises(ValueError, match="not present"):
        dyn.delete_edge(*absent)
    with pytest.raises(ValueError, match="finite and > 0"):
        dyn.add_edge(*absent, 0.0)
    with pytest.raises(ValueError, match="finite and > 0"):
        dyn.update_edge(*live, -1.0)
    with pytest.raises(ValueError, match="finite and > 0"):
        dyn.add_edge(*absent, float("inf"))
    with pytest.raises(ValueError, match="self-loops"):
        dyn.delete_edge(4, 4)
    with pytest.raises(IndexError):
        dyn.add_edge(0, 20, 1.0)
    with pytest.raises(ValueError, match="unknown edit op"):
        dyn.apply(("upsert", 0, 1, 2.0))
    assert dyn.version == 0 and len(dyn.commit()) == 0   # nothing leaked


def test_overlay_commit_coalesces_cancelling_edits():
    cg = C.random_csr_graph(30, 90, seed=2)
    dyn = DynamicGraph(cg)
    live = (int(cg.indices[0]), int(cg.dst_ids()[0]))
    w0 = dyn.weight_of(*live)
    # add then delete a new edge, and update a live edge back to its
    # original weight: net nothing happened
    pair = next((a, b) for a in range(30) for b in range(a + 1, 30)
                if not dyn.has_edge(a, b))
    dyn.add_edge(*pair, 5.0)
    dyn.delete_edge(*pair)
    dyn.update_edge(*live, 77.0)
    dyn.update_edge(*live, w0)
    batch = dyn.commit()
    assert len(batch) == 0 and dyn.version == 0


def test_overlay_base_arrays_untouched_and_growth():
    cg = C.random_csr_graph(40, 120, seed=3)
    w_before = cg.weights.copy()
    # compact_threshold=None: growth (not compaction) is the point here
    dyn = DynamicGraph(cg, overlay_capacity=2, compact_threshold=None)
    rng = np.random.default_rng(0)
    added = []
    for _ in range(7):                       # forces capacity growth 2->8
        while True:
            a, b = int(rng.integers(40)), int(rng.integers(40))
            if a != b and not dyn.has_edge(a, b):
                break
        dyn.add_edge(a, b, 2.0)
        added.append((a, b))
    dyn.commit()
    # 7 undirected edges = 14 overlay arcs, grown well past capacity 2
    assert dyn.overlay_used == 14 and dyn.overlay_capacity >= 14
    assert np.array_equal(cg.weights, w_before)     # base untouched
    assert not cg.weights.flags.writeable           # and still frozen
    ref = _serial(dyn, 0)
    got = solve_dynamic(dyn, 0)
    assert np.array_equal(got.dist, ref.dist)


def test_overlay_compaction_preserves_graph_and_version():
    cg = C.random_csr_graph(60, 180, seed=4)
    dyn = DynamicGraph(cg, overlay_capacity=64, compact_threshold=4)
    rng = np.random.default_rng(1)
    before = None
    for _ in range(3):
        _mixed_edits(dyn, rng, 4)
        dyn.commit()
        if before is None:
            before = dyn.snapshot()
    assert dyn.compactions >= 1
    assert dyn.overlay_used <= 4
    v = dyn.version
    snap = dyn.snapshot()
    compacted = dyn.compact()                # explicit compact: same graph
    assert dyn.version == v
    assert np.array_equal(compacted.weights, snap.weights)
    ref = _serial(dyn, 5)
    assert np.array_equal(solve_dynamic(dyn, 5).dist, ref.dist)


# ---------------------------------------------------------------------------
# pull_edge_slots: the pull twin against a naive reference
# ---------------------------------------------------------------------------

def test_pull_edge_slots_matches_naive_reference():
    cg = C.random_csr_graph(50, 200, seed=5)
    n = cg.n
    indptr = np.concatenate([cg.indptr, cg.indptr[-1:]]).astype(np.int32)
    src, w = np.asarray(cg.indices), np.asarray(cg.weights)
    rng = np.random.default_rng(2)
    dist = rng.uniform(0, 30, n).astype(np.float32)
    dist[rng.uniform(size=n) < 0.3] = np.inf
    rows = np.flatnonzero(rng.uniform(size=n) < 0.4).astype(np.int32)
    fids = np.full(n, n, np.int32)
    fids[: rows.size] = rows
    starts = indptr[fids]
    degs = indptr[np.minimum(fids + 1, n)] - starts
    degs[fids == n] = 0
    off = np.cumsum(degs) - degs
    E = int(degs.sum())
    nd = pull_edge_slots(
        jnp.asarray(dist), jnp.asarray(fids), jnp.asarray(dist),
        jnp.asarray(starts), jnp.asarray(off), jnp.int32(E),
        jnp.asarray(src), jnp.asarray(w), chunk=16, drop_id=jnp.int32(n))
    want = dist.copy()
    for r in rows:
        lo, hi = int(cg.indptr[r]), int(cg.indptr[r + 1])
        for p in range(lo, hi):
            want[r] = min(want[r],
                          np.float32(dist[src[p]] + w[p]))
    assert np.array_equal(np.asarray(nd), want)


def test_zero_base_degree_overlay_sources_in_sink_runs_bitwise():
    """Vertices with no base out-arc sit in runs longer than chunk=4 of the
    compaction, and two of them go on only through overlay arcs: the
    frontier solve and a repair must keep them in the active set and
    match the heap oracle (integer weights: exact f32 sums) bitwise."""
    n = 30
    arcs = [(0, v, 10 + v) for v in range(1, 9)] + [(0, 9, 1)]
    arcs += [(v, v + 1, 4) for v in range(9, n - 1)]
    e = np.array([(u, v) for u, v, _ in arcs])
    w = np.array([x for _, _, x in arcs], np.float64)
    cg = C.csr_from_edge_list(n, e, w, directed=True)
    assert np.all(np.diff(cg.out_csr()[0])[1:9] == 0)     # sinks in base
    dyn = DynamicGraph(cg, compact_threshold=None)
    prev = solve_dynamic(dyn, 0, chunk=4)
    dyn.add_edge(3, 20, 1.0)                      # overlay out of a sink
    dyn.add_edge(6, 27, 2.0)
    batch = dyn.commit()
    snap = dyn.snapshot()
    want = dijkstra_oracle(snap, 0).astype(np.float32)
    assert want[20] == 14.0 and want[27] == 18.0  # through the overlay
    res = solve_dynamic(dyn, 0, chunk=4)
    assert np.array_equal(res.dist, want)
    assert np.array_equal(res.dist, _serial(dyn, 0).dist)
    rep, _ = repair_sssp(dyn, prev, batch, chunk=4)
    assert np.array_equal(rep.dist, want)
    assert np.array_equal(rep.pred, res.pred)


# ---------------------------------------------------------------------------
# repair exactness: bitwise vs serial on the mutated snapshot
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,seed", [(60, 180, 0), (200, 600, 1),
                                      (150, 300, 2)])
def test_repair_chained_mixed_batches_bitwise_vs_serial(n, m, seed):
    cg = C.random_csr_graph(n, m, seed=seed)
    dyn = DynamicGraph(cg, overlay_capacity=16)
    rng = np.random.default_rng(seed)
    res = solve_dynamic(dyn, 0)
    for rnd in range(5):
        _mixed_edits(dyn, rng, 4)
        res, stats = repair_sssp(dyn, res, dyn.commit())
        ref = _serial(dyn, 0)
        assert np.array_equal(res.dist, ref.dist), rnd
        assert np.array_equal(res.pred, ref.pred), rnd


def test_repair_each_direction_and_disconnection_reconnection():
    # a path graph: every repair direction has a deterministic effect
    edges = np.stack([np.arange(11), np.arange(1, 12)], 1)
    cg = C.csr_from_edge_list(12, edges, np.full(11, 2.0, np.float32))
    dyn = DynamicGraph(cg)
    res = solve_dynamic(dyn, 0)
    # decrease
    dyn.update_edge(3, 4, 0.5)
    res, st = repair_sssp(dyn, res, dyn.commit())
    assert np.array_equal(res.dist, _serial(dyn, 0).dist) and st.cone == 0
    # increase (tree arc -> cone of everything downstream)
    dyn.update_edge(3, 4, 10.0)
    res, st = repair_sssp(dyn, res, dyn.commit())
    assert np.array_equal(res.dist, _serial(dyn, 0).dist) and st.cone == 8
    # delete: disconnects the tail
    dyn.delete_edge(5, 6)
    res, st = repair_sssp(dyn, res, dyn.commit())
    ref = _serial(dyn, 0)
    assert np.array_equal(res.dist, ref.dist)
    assert np.isinf(res.dist[6:]).all() and np.all(res.pred[6:] == -1)
    # insert: reconnects through a different vertex
    dyn.add_edge(2, 9, 1.0)
    res, st = repair_sssp(dyn, res, dyn.commit())
    ref = _serial(dyn, 0)
    assert np.array_equal(res.dist, ref.dist)
    assert np.array_equal(res.pred, ref.pred)
    assert np.isfinite(res.dist).all()


def test_repair_shortcut_when_batch_cannot_touch_row():
    cg = C.random_csr_graph(100, 300, seed=6)
    dyn = DynamicGraph(cg)
    res = solve_dynamic(dyn, 0)
    # increase a NON-tree arc: provably a no-op for this source's row
    pred = res.pred
    arc = next((int(u), int(v)) for u, v in
               zip(cg.indices, cg.dst_ids())
               if pred[v] != u and pred[u] != v)
    dyn.update_edge(arc[0], arc[1],
                    float(dyn.weight_of(*arc)) + 50.0)
    res2, st = repair_sssp(dyn, res, dyn.commit())
    assert st.shortcut and res2 is res
    ref = _serial(dyn, 0)
    assert np.array_equal(res2.dist, ref.dist)
    assert np.array_equal(res2.pred, ref.pred)


def test_repair_with_delta_schedule_bitwise():
    cg = C.random_csr_graph(150, 450, seed=7)
    dyn = DynamicGraph(cg)
    res = solve_dynamic(dyn, 3)
    rng = np.random.default_rng(3)
    _mixed_edits(dyn, rng, 6)
    res, _ = repair_sssp(dyn, res, dyn.commit(), delta=25.0)
    ref = _serial(dyn, 3)
    assert np.array_equal(res.dist, ref.dist)


def test_repair_sublinear_vs_full_resolve():
    cg = C.random_csr_graph(2000, 6000, seed=8)
    dyn = DynamicGraph(cg)
    res = solve_dynamic(dyn, 0)
    rng = np.random.default_rng(4)
    _mixed_edits(dyn, rng, 2)
    res, _ = repair_sssp(dyn, res, dyn.commit())
    full = solve_dynamic(dyn, 0)
    assert np.array_equal(res.dist, full.dist)
    assert res.edges_relaxed < full.edges_relaxed


def _dyn_corpus():
    sparse = [(n, 3 * n) for n, _ in
              [(10, 0), (100, 0), (1000, 0), (2000, 0), (10000, 0)]]
    return [pytest.param(n, m,
                         marks=[pytest.mark.slow] if n >= 10000 else [],
                         id=f"n{n}")
            for n, m in sparse]


@pytest.mark.parametrize("n,m", _dyn_corpus())
def test_repair_paper_corpus_bitwise_vs_serial(n, m):
    """The acceptance sweep: one mixed mutation batch per corpus point,
    repaired distances bitwise-equal to a fresh serial solve on the
    mutated graph (Table II sparse shape through n=10000)."""
    cg = C.random_csr_graph(n, m, seed=n)
    dyn = DynamicGraph(cg, overlay_capacity=16)
    res = solve_dynamic(dyn, 0)
    rng = np.random.default_rng(n)
    _mixed_edits(dyn, rng, min(8, max(2, n // 100)))
    res, _ = repair_sssp(dyn, res, dyn.commit())
    ref = _serial(dyn, 0)
    assert np.array_equal(res.dist, ref.dist)
    assert np.array_equal(res.pred, ref.pred)


def test_repair_jit_cache_stable_across_versions():
    from repro.dynamic.repair import sssp_repair

    if not hasattr(sssp_repair, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    cg = C.random_csr_graph(120, 360, seed=9)
    # no auto-compaction: shape stability across versions is the point
    dyn = DynamicGraph(cg, overlay_capacity=64, compact_threshold=None)
    res = solve_dynamic(dyn, 0)
    rng = np.random.default_rng(5)
    sizes = []
    for _ in range(5):
        _mixed_edits(dyn, rng, 3)          # same pad caps every round
        out, st = repair_sssp(dyn, res, dyn.commit())
        if not st.shortcut:
            res = out
            sizes.append(sssp_repair._cache_size())
    # first non-shortcut call compiles; every later one hits the cache
    assert len(sizes) >= 2 and sizes[-1] == sizes[0]


def test_repair_requires_pred():
    cg = C.random_csr_graph(30, 90, seed=10)
    dyn = DynamicGraph(cg)
    res = solve_dynamic(dyn, 0)
    res.pred = None
    dyn.delete_edge(int(cg.indices[0]), int(cg.dst_ids()[0]))
    with pytest.raises(ValueError, match="pred"):
        repair_sssp(dyn, res, dyn.commit())


# ---------------------------------------------------------------------------
# dynamic sweeps: the unchanged core engines on overlay operands
# ---------------------------------------------------------------------------

def test_dynamic_sweeps_drive_core_engines_bitwise():
    cg = C.random_csr_graph(90, 270, seed=11)
    dyn = DynamicGraph(cg)
    rng = np.random.default_rng(6)
    _mixed_edits(dyn, rng, 10)
    dyn.commit()
    snap = dyn.snapshot()
    ops = dyn.dyn_ops()
    # bellman fixpoint with the dynamic segment sweep
    d, _, _, _ = sssp_bellman_csr(ops, jnp.int32(4), n=dyn.n,
                                  sweep_fn=dynamic_segment_sweep)
    assert np.array_equal(np.asarray(d),
                          shortest_paths(snap, 4, engine="serial").dist)
    # batched multisource with the vmapped sweep
    D, _, _ = sssp_multisource_csr(ops, jnp.asarray([0, 7, 33], jnp.int32),
                                   n=dyn.n,
                                   sweep_fn=dynamic_segment_sweep_multi)
    for i, s in enumerate((0, 7, 33)):
        assert np.array_equal(
            np.asarray(D)[i],
            shortest_paths(snap, s, engine="serial").dist)
    # frontier with the dynamic flat sweep + target early exit
    full = shortest_paths(snap, 2, engine="serial").dist
    d, _, _, _, _ = sssp_frontier(ops, jnp.int32(2), n=dyn.n,
                                  sweep_fn=make_dynamic_flat_sweep_fn(),
                                  target=jnp.int32(60))
    assert np.asarray(d)[60] == full[60]


# ---------------------------------------------------------------------------
# serve integration: mutation ticks, selective invalidation, landmarks
# ---------------------------------------------------------------------------

def _dyn_stack(n=150, seed=12, **kw):
    cg = C.random_csr_graph(n, 3 * n, seed=seed)
    dyn = DynamicGraph(cg, overlay_capacity=32)
    registry = GraphRegistry(byte_budget=kw.pop("budget", None))
    cache = DistanceCache(capacity=kw.pop("cache_rows", 64))
    sched = MicroBatchScheduler(registry, cache, max_batch=8, **kw)
    registry.register("g", dyn, landmarks=kw.pop("landmarks", 0))
    return dyn, registry, cache, sched


def test_mutate_keeps_unaffected_rows_and_repairs_affected():
    dyn, registry, cache, sched = _dyn_stack()
    handle = registry.get("g")
    for s in (3, 50, 90):
        sched.submit("g", s)
    sched.drain()
    assert len(cache) == 3
    batches_before = sched.engine_batches
    # a far-away increase on a non-tree arc of nothing: add+delete a
    # fresh edge's weight bump cannot exist -> use an isolated update:
    # bump one arc hugely; rows with slack arcs survive, tight ones repair
    u, v = int(dyn.base.indices[0]), int(dyn.base.dst_ids()[0])
    registry.mutate("g", [("update", u, v,
                           float(dyn.weight_of(u, v)) + 60.0)])
    assert sched.rows_kept + sched.rows_repaired + \
        sched.rows_invalidated == 3
    assert sched.rows_invalidated == 0          # repair capacity covers all
    # every surviving row is exact for the NEW version and keyed to it
    for s in (3, 50, 90):
        row = cache.peek(handle.row_key(s))
        assert row is not None
        assert np.array_equal(row, _serial(dyn, s).dist)
    # re-query: all served from cache, no new engine work
    for s in (3, 50, 90):
        sched.submit("g", s)
    answers = sched.drain()
    assert all(a.via == "cache" for a in answers)
    assert sched.engine_batches == batches_before


def test_mutate_invalidates_when_repair_budget_exhausted():
    dyn, registry, cache, sched = _dyn_stack(repair_rows=0)
    for s in (3, 50):
        sched.submit("g", s)
    sched.drain()
    # delete a tree arc of row 3 so it is genuinely affected
    res = _serial(dyn, 3)
    v = int(np.flatnonzero(res.pred == 3)[0])
    registry.mutate("g", [("delete", 3, v)])
    assert sched.rows_repaired == 0
    assert sched.rows_invalidated >= 1
    sched.submit("g", 3)
    (ans,) = sched.drain()
    assert ans.via == "batch"                   # re-solved, not stale
    assert np.array_equal(ans.value, _serial(dyn, 3).dist)


def test_mutation_tick_orders_before_queries():
    dyn, registry, cache, sched = _dyn_stack()
    pair = next((a, b) for a in range(dyn.n) for b in range(a + 1, dyn.n)
                if not dyn.has_edge(a, b))
    sched.submit_mutation("g", "add", pair[0], pair[1], 0.01)
    sched.submit("g", pair[0])
    ack, ans = sched.tick()
    assert ack.via == "mutate" and ack.value == 1
    assert registry.get("g").version == 1
    # the query in the SAME tick sees the post-mutation graph
    assert np.array_equal(ans.value, _serial(dyn, pair[0]).dist)


def test_mutate_batch_is_atomic_on_invalid_edit():
    dyn, registry, cache, sched = _dyn_stack()
    before = dyn.snapshot()
    pair = next((a, b) for a in range(dyn.n) for b in range(a + 1, dyn.n)
                if not dyn.has_edge(a, b))
    with pytest.raises(ValueError, match="not present"):
        registry.mutate("g", [("add", pair[0], pair[1], 1.0),
                              ("delete", pair[0], pair[1]),
                              ("delete", pair[0], pair[1])])  # invalid
    # the valid prefix must have been rolled back, not left pending
    assert dyn.version == 0 and not dyn.has_edge(*pair)
    assert len(dyn.commit()) == 0
    after = dyn.snapshot()
    assert np.array_equal(before.weights, after.weights)
    assert np.array_equal(before.indices, after.indices)


def test_mutate_static_graph_raises_and_scheduler_acks_error():
    cg = C.random_csr_graph(40, 120, seed=13)
    registry = GraphRegistry()
    sched = MicroBatchScheduler(registry, DistanceCache(8))
    registry.register("s", cg)
    with pytest.raises(ValueError, match="static"):
        registry.mutate("s", [("delete", 0, 1)])
    sched.submit_mutation("s", "add", 0, 1, 2.0)
    sched.submit_mutation("nope", "add", 0, 1, 2.0)
    acks = sched.tick()
    assert [a.via for a in acks] == ["error", "error"]
    assert sched.last_mutation_error


def test_landmarks_stale_only_when_touched_and_lazily_refreshed():
    dyn, registry, cache, sched = _dyn_stack(n=120, seed=14)
    handle = registry.get("g")
    handle.landmarks = None
    from repro.serve import build_landmarks
    handle.landmarks = build_landmarks(
        dyn, 5, csr_ops=handle.csr_ops(),
        sweep_fn=handle.multisource_sweep_fn())
    # an untouched far corner: add+delete of a *slack* arc... use a
    # weight bump on an arc slack for EVERY landmark row
    D = handle.landmarks.D
    arc = None
    for u, v, w in zip(dyn.base.indices, dyn.base.dst_ids(),
                       dyn.base.weights):
        u, v = int(u), int(v)
        if all(np.float32(D[k, u] + np.float32(w)) != D[k, v]
               and np.float32(D[k, v] + np.float32(w)) != D[k, u]
               for k in range(5)):
            arc = (u, v, float(w))
            break
    assert arc is not None
    registry.mutate("g", [("update", arc[0], arc[1], arc[2] + 5.0)])
    assert not handle.landmarks_stale            # no landmark row touched
    # now delete a tree arc of landmark 0's row: must stale + refresh
    lm = int(handle.landmarks.ids[0])
    pred = _serial(dyn, lm).pred
    v = int(np.flatnonzero(pred == lm)[0])
    registry.mutate("g", [("delete", lm, v)])
    assert handle.landmarks_stale
    refreshes = handle.landmark_refreshes
    ls = handle.landmarks_ready()                # lazy re-solve happens HERE
    assert handle.landmark_refreshes == refreshes + 1
    assert not handle.landmarks_stale
    for k in range(ls.k):
        assert np.array_equal(ls.D[k],
                              _serial(dyn, int(ls.ids[k])).dist)
    # served landmark answers stay engine rows
    sched.submit("g", int(ls.ids[0]))
    (ans,) = sched.drain()
    assert ans.via == "landmark"
    assert np.array_equal(ans.value, _serial(dyn, int(ls.ids[0])).dist)


def test_eviction_purges_every_version_of_a_mutated_graph():
    """The registry-eviction interplay: evicting a mutated (versioned)
    graph purges the cache rows of EVERY version — including rows a
    buggy reconciliation might have stranded under old versions — and
    the landmark state goes with the handle."""
    dyn, registry, cache, sched = _dyn_stack(budget=None)
    pair = next((a, b) for a in range(dyn.n) for b in range(a + 1, dyn.n)
                if not dyn.has_edge(a, b))
    sched.submit("g", 3)
    sched.drain()
    registry.mutate("g", [("add", pair[0], pair[1], 1.0)])
    sched.submit("g", 7)
    sched.drain()
    # strand an extra row under a long-gone version on purpose
    cache.put(("g", 0, 11), np.zeros(dyn.n, np.float32))
    versions = {k[1] for k in cache.keys_for("g")}
    assert len(versions) >= 2                   # multi-version state exists
    # replacing the name evicts the old handle -> every version purged
    registry.register("g", C.random_csr_graph(50, 150, seed=99))
    assert cache.keys_for("g") == []
    assert registry.stats()["evicted"] == 1


# ---------------------------------------------------------------------------
# churn traces
# ---------------------------------------------------------------------------

def test_churn_trace_deterministic_and_self_consistent():
    cg = C.random_csr_graph(100, 300, seed=15)
    a = make_churn_trace([("g", cg)], num_events=80, rate=100,
                         mutate_frac=0.3, seed=4, hot_seed=9)
    b = make_churn_trace([("g", cg)], num_events=80, rate=100,
                         mutate_frac=0.3, seed=4, hot_seed=9)
    assert a == b
    n_mut = sum(isinstance(e, MutationEvent) for e in a)
    assert 0 < n_mut < 80
    # every mutation is valid when applied in order (self-consistency)
    dyn = DynamicGraph(cg, overlay_capacity=16)
    for e in a:
        if isinstance(e, MutationEvent):
            dyn.apply((e.op, e.u, e.v) if e.w is None
                      else (e.op, e.u, e.v, e.w))
    dyn.commit()
    with pytest.raises(ValueError, match="undirected"):
        make_churn_trace(
            [("d", C.random_csr_graph(30, 90, seed=1, directed=True))],
            num_events=5, rate=10)


def test_churn_replay_end_to_end_bitwise():
    """The tentpole invariant end to end: replay a churn trace through
    registry -> scheduler -> dynamic engines -> cache repair, checking
    every answer bitwise against serial on the answer-time snapshot."""
    cg = C.random_csr_graph(120, 360, seed=16)
    dyn = DynamicGraph(cg, overlay_capacity=32, compact_threshold=24)
    registry = GraphRegistry()
    cache = DistanceCache(capacity=32)
    sched = MicroBatchScheduler(registry, cache, max_batch=4)
    registry.register("g", dyn, landmarks=4)
    events = make_churn_trace([("g", cg)], num_events=90, rate=1e4,
                              mutate_frac=0.3, seed=6, hot_seed=2)
    rows: dict = {}
    for e in events:
        if isinstance(e, MutationEvent):
            sched.submit_mutation(e.graph, e.op, e.u, e.v, e.w)
        else:
            sched.submit(e.graph, e.source, e.target)
        for a in sched.drain():
            if a.via == "mutate":
                continue
            assert a.via != "error"
            q = a.query
            key = (dyn.version, q.source)
            if key not in rows:
                rows[key] = _serial(dyn, q.source).dist
            ref = rows[key]
            if q.target is None:
                assert np.array_equal(a.value, ref), (q, a.via)
            else:
                got, want = np.float32(a.value), ref[q.target]
                assert got == want or (np.isinf(got) and np.isinf(want)), \
                    (q, a.via)
    assert registry.get("g").version > 0
    s = sched.stats()
    assert s["rows_kept"] + s["rows_repaired"] + s["rows_invalidated"] > 0


# ---------------------------------------------------------------------------
# row_affected: the keep/invalidate test is sound and not vacuous
# ---------------------------------------------------------------------------

def test_row_affected_sound_and_selective():
    cg = C.random_csr_graph(80, 240, seed=17)
    dyn = DynamicGraph(cg)
    rows = {s: _serial(dyn, s).dist for s in range(0, 80, 7)}
    rng = np.random.default_rng(7)
    kept_any = False
    for _ in range(6):
        _mixed_edits(dyn, rng, 3)
        batch = dyn.commit()
        for s, row in rows.items():
            affected = row_affected(row, batch, dyn.directed)
            new = _serial(dyn, s).dist
            if not affected:
                # claimed unaffected => must still be the exact fixpoint
                assert np.array_equal(row, new), s
                kept_any = True
            rows[s] = new
    assert kept_any                     # the test is not vacuously sound
