"""Frontier-compacted engine + batched multi-source CSR correctness.

Pins down the PR's perf claims as testable invariants: the frontier
engines agree bitwise with every other engine (same f32 path-sum minima),
the edges-relaxed counter proves the O(frontier out-degree) sweeps do
strictly less work than bellman_csr's O(m) sweeps where frontiers are
narrow, and the batched CSR engine equals S independent solves.
"""
import math

import numpy as np
import pytest

import jax.numpy as jnp
from jax import lax

from conftest import dijkstra_oracle, finite_close
from repro.core import csr as C
from repro.core import graph as G
from repro.core.api import recover_pred, shortest_paths
from repro.core.bellman_csr import csr_operands, sssp_multisource_csr
from repro.core import frontier as FR
from repro.core.frontier import (frontier_operands, make_flat_sweep_fn,
                                 pull_edge_slots, relax_edge_slots,
                                 relax_edge_slots_multi, sssp_frontier)
from repro.kernels.frontier_relax import (frontier_cand_block,
                                          frontier_cand_ref,
                                          frontier_relax_ref)

FRONTIER = ("frontier", "frontier_kernel")


def _skewed_hub(n=120, spokes=100):
    """Heavy-tailed out-degree: vertex 0 fans out to ``spokes`` vertices
    (the shape where padded-ELL widths blow up and frontier compaction
    must still relax every window correctly)."""
    hub = np.stack([np.zeros(spokes, np.int64),
                    np.arange(1, spokes + 1)], 1)
    path = np.stack([np.arange(n - 1), np.arange(1, n)], 1)
    edges = np.concatenate([hub, path])
    return G.csr_from_edge_list(n, edges,
                                np.arange(1.0, len(edges) + 1.0))


def _cases():
    return [
        pytest.param(G.random_graph(50, 1225, seed=1), id="dense50"),
        pytest.param(G.random_graph(100, 300, seed=2), id="sparse100"),
        pytest.param(G.random_graph(60, 240, seed=3, directed=True),
                     id="directed60"),
        pytest.param(G.random_graph(50, 60, seed=4, connected=False),
                     id="disconnected50"),
        pytest.param(_skewed_hub(), id="skewed-hub"),
        pytest.param(G.from_edge_list(1, np.zeros((0, 2), np.int64),
                                      np.zeros(0)), id="single-vertex"),
    ]


# ---------------------------------------------------------------------------
# frontier engines vs the independent heap oracle (+ bitwise vs bellman_csr)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", FRONTIER)
@pytest.mark.parametrize("g", _cases())
def test_frontier_matches_oracle(engine, g):
    ref = dijkstra_oracle(g, 0)
    res = shortest_paths(g, 0, engine=engine)
    assert finite_close(ref, res.dist)
    assert np.array_equal(np.isfinite(ref), np.isfinite(res.dist))
    # same candidate minima as the whole-graph sweep: bitwise equality
    base = shortest_paths(g, 0, engine="bellman_csr")
    assert np.array_equal(base.dist, res.dist)


@pytest.mark.parametrize("n,m", [(100, 300), (1000, 3000)])
def test_frontier_bitwise_matches_serial_paper_corpus(n, m):
    g = G.paper_graph(n, m, seed=n + m)
    ref = shortest_paths(g, 0, engine="serial").dist
    for engine in FRONTIER:
        got = shortest_paths(g, 0, engine=engine).dist
        assert np.array_equal(ref, got), engine


@pytest.mark.parametrize("delta", [5.0, 30.0, 1000.0])
def test_frontier_delta_schedule_same_fixpoint(delta):
    g = G.random_graph(120, 480, seed=9)
    base = shortest_paths(g, 0, engine="frontier")
    res = shortest_paths(g, 0, engine="frontier", delta=delta)
    assert np.array_equal(base.dist, res.dist)
    assert np.array_equal(base.pred, res.pred)


def test_frontier_small_chunk_multi_step_inner_loop():
    """chunk=8 forces many inner edge-slot steps per sweep; result must be
    bitwise identical to the single-chunk default."""
    cg = C.random_csr_graph(80, 320, seed=13)
    ops = frontier_operands(cg)
    d_ref, p_ref, s_ref, e_ref, c_ref = sssp_frontier(ops, jnp.int32(0),
                                                      n=cg.n)
    d, p, s, e, c = sssp_frontier(ops, jnp.int32(0), n=cg.n, chunk=8)
    assert np.array_equal(np.asarray(d_ref), np.asarray(d))
    assert np.array_equal(np.asarray(p_ref), np.asarray(p))
    assert (int(s_ref), int(e_ref)) == (int(s), int(e))
    assert bool(c_ref) and bool(c)


def test_frontier_pred_tree_valid_and_matches_csr():
    g = G.random_graph(90, 350, seed=11)
    base = shortest_paths(g, 0, engine="bellman_csr")
    for engine in FRONTIER:
        res = shortest_paths(g, 0, engine=engine)
        # identical fixpoint + identical recovery -> identical tree
        assert np.array_equal(base.pred, res.pred), engine


# ---------------------------------------------------------------------------
# the perf claim, as an invariant: sweeps touch only the frontier's edges
# ---------------------------------------------------------------------------

def test_frontier_relaxes_fewer_edges_than_bellman_csr_on_path():
    """Path graph: bellman_csr relaxes all 2(n-1) arcs for each of ~n
    sweeps; the frontier engine's active set is one vertex per sweep, so
    its total must be strictly (and asymptotically) smaller."""
    n = 64
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], 1)
    cg = G.csr_from_edge_list(n, edges, np.ones(n - 1))
    rf = shortest_paths(cg, 0, engine="frontier")
    rb = shortest_paths(cg, 0, engine="bellman_csr")
    assert rb.edges_relaxed == rb.sweeps * cg.nnz
    assert rf.edges_relaxed < rb.edges_relaxed
    # one frontier vertex per sweep, <= 2 arcs each (undirected path)
    assert rf.edges_relaxed <= 2 * n


def test_frontier_edges_counter_exact_on_star():
    """Star from the hub: sweep 1 relaxes the hub's out-degree, sweep 2
    relaxes the leaves' back-arcs, then one empty-improvement sweep."""
    n = 9
    edges = np.stack([np.zeros(n - 1, np.int64), np.arange(1, n)], 1)
    cg = G.csr_from_edge_list(n, edges, np.ones(n - 1))
    res = shortest_paths(cg, 0, engine="frontier")
    assert res.edges_relaxed == (n - 1) + (n - 1)
    assert res.sweeps == 2


# ---------------------------------------------------------------------------
# batched multi-source CSR
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("g", _cases())
def test_multisource_csr_rows_match_oracle(g):
    n = g.n if hasattr(g, "n") else g.shape[0]
    srcs = np.unique(np.array([0, n // 2, n - 1], np.int32))
    res = shortest_paths(g, srcs, engine="multisource_csr")
    assert res.dist.shape == (len(srcs), n)
    assert res.pred is None
    for i, s in enumerate(srcs):
        assert finite_close(dijkstra_oracle(g, int(s)), res.dist[i]), s


def test_multisource_csr_bitwise_matches_single_source_and_dense_batch():
    g = G.random_graph(80, 400, seed=3)
    srcs = np.array([0, 17, 42, 63], np.int32)
    res = shortest_paths(g, srcs, engine="multisource_csr")
    dense = shortest_paths(g, srcs, engine="multisource")
    assert np.array_equal(res.dist, dense.dist)
    for i, s in enumerate(srcs):
        single = shortest_paths(g, int(s), engine="bellman_csr")
        assert np.array_equal(single.dist, res.dist[i]), s


def test_multisource_csr_accepts_csr_input_no_densify(monkeypatch):
    cg = C.random_csr_graph(500, 1500, seed=8)
    monkeypatch.setattr(
        C.CsrGraph, "to_dense",
        lambda self: pytest.fail("multisource_csr densified the graph"),
    )
    res = shortest_paths(cg, np.array([0, 250], np.int32),
                         engine="multisource_csr")
    assert np.isfinite(res.dist).all()


# ---------------------------------------------------------------------------
# recover_pred (satellite: SsspResult.pred is None for multisource)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["multisource", "multisource_csr"])
def test_recover_pred_builds_valid_trees(engine):
    g = G.random_graph(90, 350, seed=11)
    srcs = np.array([0, 30, 60], np.int32)
    res = shortest_paths(g, srcs, engine=engine)
    assert res.pred is None
    arg = g.to_csr() if engine == "multisource_csr" else g
    P = recover_pred(res, arg)
    assert P.shape == res.dist.shape
    for i, s in enumerate(srcs):
        d, p = res.dist[i], P[i]
        assert p[s] == -1
        for v in range(g.n):
            if v == s or not np.isfinite(d[v]):
                continue
            u = p[v]
            assert u >= 0 and u != v
            assert np.isclose(d[v], d[u] + g.adj[u, v], rtol=1e-5)
        # same helper as the single-source engines -> identical tree
        eng = "bellman_csr" if engine == "multisource_csr" else "bellman"
        assert np.array_equal(
            P[i], shortest_paths(g, int(s), engine=eng).pred)


def test_recover_pred_passthrough_and_source_inference():
    g = G.random_graph(40, 120, seed=6)
    res = shortest_paths(g, 0, engine="bellman_csr")
    assert recover_pred(res, g.to_csr()) is res.pred
    # sources stripped -> inferred from the zero entry of each row
    ms = shortest_paths(g, np.array([7], np.int32), engine="multisource")
    ms.sources = None
    P = recover_pred(ms, g)
    assert np.array_equal(
        P[0], shortest_paths(g, 7, engine="bellman").pred)


# ---------------------------------------------------------------------------
# out-CSR container views + the Pallas candidate kernel vs its oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("directed", [False, True])
def test_out_csr_is_the_transpose(directed):
    cg = C.random_csr_graph(60, 240, seed=21, directed=directed)
    indptr, out_dst, out_w = cg.out_csr()
    assert indptr[-1] == cg.nnz
    adj = cg.to_dense().adj
    for u in range(cg.n):
        dsts = out_dst[indptr[u]:indptr[u + 1]]
        ws = out_w[indptr[u]:indptr[u + 1]]
        assert np.all(np.diff(dsts) > 0)            # sorted, no dup arcs
        for v, w in zip(dsts, ws):
            assert adj[u, v] == w
        assert len(dsts) == np.isfinite(np.delete(adj[u], u)).sum()


def test_out_ell_padding_is_inert():
    cg = _skewed_hub()
    idx, w = cg.out_ell()
    indptr, _, _ = cg.out_csr()
    deg = np.diff(indptr)
    assert idx.shape[1] >= deg.max() and idx.shape[1] % 8 == 0
    for u in range(cg.n):
        assert np.all(np.isfinite(w[u, :deg[u]]))
        assert np.all(np.isinf(w[u, deg[u]:]))
        assert np.all(idx[u, deg[u]:] == 0)


@pytest.mark.parametrize("n,F", [(64, 16), (100, 100), (137, 40)])
def test_kernel_cand_bitwise_matches_ref(n, F):
    cg = C.random_csr_graph(n, 4 * n, seed=n)
    ell_idx, ell_w = cg.out_ell()
    rng = np.random.default_rng(n)
    d = rng.uniform(0, 50, n).astype(np.float32)
    d[rng.uniform(size=n) < 0.3] = np.inf
    fids = np.concatenate([rng.permutation(n)[:F - F // 4],
                           np.full(F // 4, n)]).astype(np.int32)  # sentinels
    dist = jnp.asarray(d)
    w_rows = jnp.asarray(ell_w)[jnp.minimum(jnp.asarray(fids), n - 1)]
    ref = frontier_cand_ref(dist, jnp.asarray(fids), w_rows)
    out = frontier_cand_block(dist, jnp.asarray(fids), w_rows,
                              interpret=True)
    assert np.array_equal(np.asarray(ref), np.asarray(out))


def test_kernel_sweep_bitwise_matches_flat_sweep():
    """Full-sweep agreement: the kernel ELL path and the flat-CSR path
    scatter-min the same candidate multiset."""
    from repro.kernels.frontier_relax.ops import make_frontier_sweep_fn

    cg = C.random_csr_graph(90, 360, seed=33)
    ops = frontier_operands(cg, with_ell=True)
    for src in (0, 45):
        a = sssp_frontier(ops, jnp.int32(src), n=cg.n)
        b = sssp_frontier(ops, jnp.int32(src), n=cg.n,
                          sweep_fn=make_frontier_sweep_fn(block_f=32,
                                                          interpret=True))
        for x, y in zip(a, b):
            assert np.array_equal(np.asarray(x), np.asarray(y))


def test_frontier_relax_ref_matches_engine_first_sweep():
    """The uncompacted oracle sweep equals one engine sweep from the
    source frontier."""
    cg = C.random_csr_graph(70, 280, seed=5)
    ops = frontier_operands(cg, with_ell=True)
    n = cg.n
    dist0 = jnp.full((n,), jnp.inf).at[0].set(0.0)
    active = dist0 < jnp.inf
    want = frontier_relax_ref(dist0, active, ops["out_ell_idx"],
                              ops["out_ell_w"])
    d1, _, _, _, _ = sssp_frontier(ops, jnp.int32(0), n=n, max_sweeps=1)
    assert np.array_equal(np.asarray(want), np.asarray(d1))


# ---------------------------------------------------------------------------
# the edge-slot walker: row cursor against a whole-array binary search
# ---------------------------------------------------------------------------

def _search_slot_minloop(nd, starts, off, E, m, F, *, chunk, emit,
                         scatter=None):
    """Reference walker: each slot's row by a binary search over all F
    compacted rows, a full chunk of slots every step.  Same signature and
    return shape as ``frontier._slot_minloop`` (the step count is None)."""
    if scatter is None:
        def scatter(nd2, tgt, cand):
            return nd2.at[tgt].min(cand, mode="drop")

    def body(carry):
        nd2, c = carry
        slots = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
        valid = slots < E
        row = jnp.clip(jnp.searchsorted(off, slots, side="right") - 1,
                       0, F - 1)
        pos = jnp.clip(starts[row] + (slots - off[row]), 0, m - 1)
        cand, tgt = emit(row, pos, valid)
        return scatter(nd2, tgt, cand), c + 1

    nd, _ = lax.while_loop(lambda c: c[1] * chunk < E, body,
                           (nd, jnp.int32(0)))
    return nd, None


_LAYOUTS = ("no-zeros", "scattered-zeros", "zero-runs", "exact-multiple",
            "empty")


def _windows(layout, chunk, seed, *, n=64, m=300):
    """Random compacted frontier windows as the compaction lays them out:
    per-row window starts into an m-arc edge array, the exclusive cumsum of
    the window lengths, and a tail of sentinel rows with no slots.
    ``zero-runs`` holds runs of zero-slot rows longer than ``chunk``;
    ``exact-multiple`` makes E a multiple of ``chunk``; ``empty`` has
    E = 0.  Returns (starts, off, E, fids)."""
    rng = np.random.default_rng(seed)
    rows = max(120, 3 * chunk + 10)
    degs = rng.integers(1, 6, rows)
    if layout in ("scattered-zeros", "exact-multiple"):
        degs[rng.uniform(size=rows) < 0.3] = 0
    elif layout == "zero-runs":
        run = chunk + 3
        for at in (0, rows // 2):                 # at the head, and inside
            degs[at:at + run] = 0
    elif layout == "empty":
        degs[:] = 0
    if layout == "exact-multiple":
        need = -int(degs.sum()) % chunk           # one more slot per row
        degs[np.flatnonzero(degs)[:need]] += 1
    starts = np.array([rng.integers(0, m - d + 1) for d in degs])
    tail = rows // 4                              # sentinel rows: no slots
    degs = np.concatenate([degs, np.zeros(tail, np.int64)])
    starts = np.concatenate([starts, np.full(tail, m)])
    fids = np.concatenate([rng.integers(0, n, rows), np.full(tail, n)])
    off = np.cumsum(degs) - degs
    E = int(degs.sum())
    if layout == "exact-multiple":
        assert E % chunk == 0 and E > 0
    return (jnp.asarray(starts, jnp.int32), jnp.asarray(off, jnp.int32),
            jnp.int32(E), jnp.asarray(fids, jnp.int32))


def _relax_form(form, chunk, starts, off, E, fids, seed, *, n=64, m=300):
    """One call of the public relax form ``form`` on random distances and
    arcs (non-integer, so a slot given the wrong row changes a minimum)."""
    rng = np.random.default_rng(seed + 1)
    F = fids.shape[0]
    nd = jnp.asarray(rng.uniform(20, 60, n), jnp.float32)
    dst = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    w = jnp.asarray(rng.uniform(0.5, 10, m), jnp.float32)
    rd = rng.uniform(0, 40, (3, F)).astype(np.float32)
    rd[rng.uniform(size=rd.shape) < 0.2] = np.inf
    drop = jnp.int32(n)
    if form == "push":
        return relax_edge_slots(nd, jnp.asarray(rd[0]), starts, off, E, dst,
                                w, chunk=chunk, drop_id=drop)
    if form == "multi":
        ND = jnp.tile(nd, (3, 1)) + jnp.arange(3, dtype=jnp.float32)[:, None]
        return relax_edge_slots_multi(ND, jnp.asarray(rd), starts, off, E,
                                      dst, w, chunk=chunk, drop_id=drop)
    src_dist = jnp.asarray(rd[0, :n])
    return pull_edge_slots(nd, fids, src_dist, starts, off, E, dst, w,
                           chunk=chunk, drop_id=drop)


@pytest.mark.parametrize("layout", _LAYOUTS)
@pytest.mark.parametrize("chunk", [1, 4, 1024])
@pytest.mark.parametrize("form", ["push", "multi", "pull"])
def test_slot_walker_bitwise_matches_search_walker(form, chunk, layout,
                                                   monkeypatch):
    """Every relax form through the row-cursor walker equals the same form
    through the whole-array search walker, bit for bit."""
    seed = 7 * chunk + _LAYOUTS.index(layout)
    starts, off, E, fids = _windows(layout, chunk, seed)
    got = _relax_form(form, chunk, starts, off, E, fids, seed)
    monkeypatch.setattr(FR, "_slot_minloop", _search_slot_minloop)
    want = _relax_form(form, chunk, starts, off, E, fids, seed)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    if layout != "empty":           # the candidates did land somewhere
        assert not np.array_equal(
            np.asarray(got),
            np.asarray(_relax_form(form, chunk, starts, off, jnp.int32(0),
                                   fids, seed)))


@pytest.mark.parametrize("layout", _LAYOUTS)
@pytest.mark.parametrize("chunk", [1, 4, 1024])
def test_slot_walker_step_count(chunk, layout):
    """ceil(E / chunk) steps when every row before the sentinel tail has a
    slot; at most ceil(E / chunk) + ceil(Z / chunk) with Z zero-slot rows
    (the tail included)."""
    starts, off, E, _ = _windows(layout, chunk, 3 + chunk)
    F = int(off.shape[0])
    degs = np.diff(np.append(np.asarray(off), int(E)))
    Z = int(np.sum(degs == 0))

    def emit(row, pos, valid):
        return (jnp.full(valid.shape, jnp.inf),
                jnp.full(valid.shape, 8, jnp.int32))

    _, steps = FR._slot_minloop(jnp.zeros(8), starts, off, E, 300, F,
                                chunk=chunk, emit=emit)
    full = math.ceil(int(E) / chunk)
    if layout == "no-zeros":
        assert int(steps) == full
    else:
        assert full <= int(steps) <= full + math.ceil(Z / chunk)


def _sink_fan(n=40):
    """Directed, integer weights: the source fans out to a run of 12 sinks
    (ids 1..12, no out-arcs) before the one vertex that goes on, which
    fans out to another run of sinks and a path with arcs back into
    them — every sweep's compaction holds a run of zero-slot rows."""
    arcs = [(0, v, 20 + v) for v in range(1, 13)] + [(0, 13, 1)]
    arcs += [(13, v, v) for v in range(14, 21)] + [(13, 21, 2)]
    arcs += [(v, v + 1, 3) for v in range(21, n - 1)]
    arcs += [(v, (v % 19) + 1, 1) for v in range(22, n, 3)]
    e = np.array([(u, v) for u, v, _ in arcs])
    w = np.array([x for _, _, x in arcs], np.float64)
    return C.csr_from_edge_list(n, e, w, directed=True)


def test_frontier_sink_run_longer_than_chunk_matches_oracle(monkeypatch):
    """chunk=4 against a compaction holding runs of more than 4 sinks: the
    walk's short steps give the heap oracle's distances exactly (integer
    weights, so f32 sums are exact) and the search walker's sweeps,
    edges_relaxed and converged."""
    cg = _sink_fan()
    ops = frontier_operands(cg)
    d, p, s, e, c = sssp_frontier(ops, jnp.int32(0), n=cg.n, chunk=4)
    assert np.array_equal(np.asarray(d),
                          dijkstra_oracle(cg, 0).astype(np.float32))
    assert np.array_equal(np.asarray(d),
                          shortest_paths(cg, 0, engine="serial").dist)
    base = make_flat_sweep_fn(4)
    monkeypatch.setattr(FR, "_slot_minloop", _search_slot_minloop)

    def search_sweep(*args):                     # a fresh jit static
        return base(*args)

    want = sssp_frontier(ops, jnp.int32(0), n=cg.n, sweep_fn=search_sweep)
    for x, y in zip((d, p, s, e, c), want):
        assert np.array_equal(np.asarray(x), np.asarray(y))
