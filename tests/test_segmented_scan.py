"""The batched relax sweep's scatter-free reduction.

``segment_relax_sweep_multi`` takes each vertex's minimum candidate with a
segmented min-scan over the destination-sorted arcs and one gather of
each run's last slot.  It must equal the sorted ``jax.ops.segment_min``
it replaced bit for bit, sweep for sweep, and its compiled shape must
follow (n, m, S) only, never the graph's degree histogram.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import csr as C
from repro.core.bellman_csr import (csr_operands, segment_relax_sweep_multi,
                                    sssp_multisource_csr)
from repro.obs import Tracer, default_registry, set_tracer
from repro.serve import DistanceCache, GraphRegistry, MicroBatchScheduler

INF = np.float32(np.inf)


def segment_min_sweep(D, csr):
    """The sweep as it was: a vmapped gather and sorted segment-min."""
    def one(d):
        via = d[csr["src"]] + csr["w"]
        return jax.ops.segment_min(via, csr["dst"], num_segments=d.shape[0],
                                   indices_are_sorted=True)
    return jnp.minimum(D, jax.vmap(one)(D))


def _in_degree_graph(degrees, n, *, seed=0):
    """Directed graph on ``n`` vertices in which vertex v < len(degrees)
    has ``degrees[v]`` incoming arcs (from the next vertices, cyclically)
    and every later vertex has none."""
    rng = np.random.default_rng(seed)
    edges = [((v + 1 + i) % n, v) for v, d in enumerate(degrees)
             for i in range(d)]
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    w = rng.uniform(1, 100, len(edges)).astype(np.float32)
    return C.csr_from_edge_list(n, edges, w, directed=True)


def _inf_weight_graph():
    cg = C.random_csr_graph(80, 240, seed=7, directed=True)
    w = np.array(cg.weights)
    w[::3] = INF
    return C.CsrGraph(cg.indptr, cg.indices, w, cg.n, directed=True)


GRAPHS = {
    # in-degree 0 at both ends and between: the +inf sentinel slot
    "sentinel": lambda: _in_degree_graph([0, 3, 0, 0, 1, 2], 12),
    # runs of exactly 2^k and 2^k + 1 arcs
    "pow2": lambda: _in_degree_graph([1, 2, 3, 4, 5, 8, 9, 16, 17], 40),
    # one vertex holds more than half the arcs
    "hub": lambda: _in_degree_graph([45] + [2] * 10, 50),
    "inf_weights": _inf_weight_graph,
    # no arcs at all: nothing to scan or gather
    "no_arcs": lambda: _in_degree_graph([0, 0], 5),
    "random": lambda: C.random_csr_graph(120, 400, seed=3),
}


def _dist(S, n, seed):
    rng = np.random.default_rng(seed)
    d = rng.uniform(0, 300, (S, n)).astype(np.float32)
    d[rng.random((S, n)) < 0.3] = INF
    d[:, 0] = 0.0
    return jnp.asarray(d)


def _bits(a):
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("S", [1, 4, 16])
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_sweep_is_bitwise_the_segment_min(name, S):
    cg = GRAPHS[name]()
    ops = csr_operands(cg)
    D = _dist(S, cg.n, seed=S)
    new = segment_relax_sweep_multi(D, ops)
    old = segment_min_sweep(D, ops)
    assert new.shape == (S, cg.n)
    np.testing.assert_array_equal(_bits(new), _bits(old))


def test_run_layout_of_the_staged_operands():
    cg = GRAPHS["pow2"]()
    rank, last, steps = cg.in_runs()
    deg = np.diff(cg.indptr)
    assert steps == math.ceil(math.log2(deg.max())) == 5
    for v in range(cg.n):
        lo, hi = cg.indptr[v], cg.indptr[v + 1]
        np.testing.assert_array_equal(rank[lo:hi], np.arange(hi - lo))
        assert last[v] == (hi - 1 if hi > lo else cg.nnz)
    # no run of two arcs: nothing to scan
    assert _in_degree_graph([1, 1, 0], 3).in_runs()[2] == 0
    assert C.csr_from_edge_list(1, np.zeros((0, 2)),
                                np.zeros(0)).in_runs()[2] == 0


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_solve_matches_the_segment_min_solve(name):
    cg = GRAPHS[name]()
    ops = csr_operands(cg)
    sources = jnp.arange(4, dtype=jnp.int32) % cg.n
    D, sweeps, conv = sssp_multisource_csr(ops, sources, n=cg.n)
    D0, sweeps0, conv0 = sssp_multisource_csr(
        ops, sources, n=cg.n, sweep_fn=segment_min_sweep)
    np.testing.assert_array_equal(_bits(D), _bits(D0))
    assert int(sweeps) == int(sweeps0)
    assert bool(conv) and bool(conv0)


def _same_size_graph(hub_degree, n=67, m=61):
    """n vertices and m arcs, vertex 0 with ``hub_degree`` of them and
    the rest spread one or two a vertex."""
    rest = m - hub_degree
    degrees = [hub_degree] + [2] * (rest // 2) + [1] * (rest % 2)
    cg = _in_degree_graph(degrees, n, seed=hub_degree)
    assert cg.nnz == m and np.diff(cg.indptr).max() == hub_degree
    return cg


def test_program_shape_does_not_follow_the_degree_histogram():
    """Largest in-degrees 15 and 17 (the batch cell's seeds give both):
    one compiled program, two scan depths."""
    registry = GraphRegistry()
    sched = MicroBatchScheduler(registry, DistanceCache(capacity=64),
                                max_batch=4)
    for name, hub in (("a", 15), ("b", 17)):
        registry.register(name, _same_size_graph(hub))
    traces = default_registry().counter("jit.retrace", fn="multisource_csr")
    before = traces.value
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        for name in ("a", "b"):
            for s in range(4):
                sched.submit(name, s)
            answers = sched.tick()
            assert [a.via for a in answers] == ["batch"] * 4
    finally:
        set_tracer(prev)
    assert traces.value - before == 1
    steps = [s.args["scan_steps"] for s in tr.spans
             if s.name == "batch_solve"]
    assert steps == [4, 5]
