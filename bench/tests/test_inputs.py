"""The benchmark's generated inputs: graphs and jobs from the seed."""
import numpy as np
import pytest
from scipy.sparse.csgraph import connected_components, dijkstra

from bench import graphs, traffic

RANDOM = {"name": "r", "generator": "random_connected", "n": 500,
          "edges": 1500, "max_weight": 100.0}
P2P = {"graphs": 1, "sources": 6, "check_sources": 3, "trace_jobs": 2,
       "job": {"kind": "p2p", "rank_exponents": [1, 3, 5, 7]}}
ROWS = {"graphs": 1, "sources": 24, "check_sources": 4, "trace_jobs": 1,
        "job": {"kind": "rows", "sources_per_job": 4}}
BIG_SEED = 2 ** 31 + 77


def same_csr(a, b):
    return (a.n == b.n and np.array_equal(a.indptr, b.indptr)
            and np.array_equal(a.indices, b.indices)
            and a.weights.tobytes() == b.weights.tobytes())


@pytest.mark.parametrize("seed", [0, BIG_SEED])
def test_the_seed_fixes_the_graph(seed):
    assert same_csr(graphs.build(RANDOM, seed), graphs.build(RANDOM, seed))
    assert not same_csr(graphs.build(RANDOM, seed),
                        graphs.build(RANDOM, seed + 1))


@pytest.mark.parametrize("seed", [1, BIG_SEED, 2 ** 40 + 3])
def test_every_seed_gives_exactly_m_edges(seed):
    """The same sizes, so the same compiled programs, on every seed."""
    g = graphs.build(RANDOM, seed)
    assert g.arcs == 2 * RANDOM["edges"]
    assert g.indptr.shape == (RANDOM["n"] + 1,)


def test_graph_is_undirected_simple_and_connected():
    g = graphs.build(RANDOM, 3)
    ptr, dst, w = g.out_csr()
    src = np.repeat(np.arange(g.n), np.diff(ptr))
    fwd = {(int(u), int(v)): float(x) for u, v, x in zip(src, dst, w)}
    assert len(fwd) == g.arcs
    assert all(u != v for u, v in fwd)
    assert all(fwd[(v, u)] == x for (u, v), x in fwd.items())
    assert np.all((g.weights >= 1) & (g.weights <= 100))
    assert connected_components(traffic._scipy_out(g))[0] == 1


def test_too_many_edges_are_refused():
    with pytest.raises(ValueError):
        graphs.build(dict(RANDOM, n=10, edges=46), 1)


@pytest.mark.parametrize("mix", [P2P, ROWS], ids=["p2p", "rows"])
def test_the_seed_fixes_the_jobs_and_their_sizes(mix):
    def jobs(seed):
        return traffic.make_jobs(mix, [graphs.build(RANDOM, seed)], seed)

    a, b, c = jobs(9), jobs(9), jobs(2 ** 40)
    assert a == b and a != c
    (warm,), drawn = a
    assert sorted(j.label for j in drawn) == sorted(j.label for j in c[1])
    sources = [s for j in drawn for s, _ in j.queries]
    per_source = 1 if mix is ROWS else len(mix["job"]["rank_exponents"])
    assert len(set(sources)) == mix["sources"]
    assert len(sources) == mix["sources"] * per_source
    assert not {s for s, _ in warm.queries} & set(sources)


def test_rows_jobs_hold_distinct_full_row_queries():
    g = graphs.build(RANDOM, 4)
    warm, jobs = traffic.make_jobs(ROWS, [g], 4)
    assert len(jobs) == ROWS["sources"] // 4
    for j in warm + jobs:
        assert len(j.queries) == 4 and all(t is None for _, t in j.queries)
        assert len({s for s, _ in j.queries}) == 4


def test_rank_targets_match_scipy_dijkstra_rank():
    g = graphs.build(RANDOM, 4)
    (warm,), jobs = traffic.make_jobs(P2P, [g], 4)
    out = traffic._scipy_out(g)
    ks = P2P["job"]["rank_exponents"]
    for i, job in enumerate(jobs):
        (s, t), = job.queries
        d = dijkstra(out, directed=True, indices=s)
        order = np.lexsort((np.arange(g.n), d))      # by distance, then id
        assert t == order[2 ** ks[i % len(ks)]]
        assert job.label == f"rank2^{ks[i % len(ks)]}"
    (s, t), = warm.queries
    assert t in out.indices[out.indptr[s]:out.indptr[s + 1]]


def test_too_many_sources_are_refused():
    g = graphs.build(RANDOM, 5)
    with pytest.raises(ValueError):
        traffic.make_jobs(dict(ROWS, sources=500), [g], 5)


def test_jobs_take_the_graphs_in_turn():
    seed = 2 ** 31 + 3
    gs = [graphs.build(RANDOM, seed, i) for i in range(3)]
    assert not same_csr(gs[0], gs[1]) and not same_csr(gs[1], gs[2])
    assert same_csr(gs[0], graphs.build(RANDOM, seed))
    warm, jobs = traffic.make_jobs(dict(ROWS, graphs=3), gs, seed)
    assert [w.graph for w in warm] == [0, 1, 2]
    assert [j.graph for j in jobs] == [0, 1, 2] * (ROWS["sources"] // 4)
    one, = traffic.make_jobs(ROWS, gs[:1], seed)[0]
    assert one == warm[0]                   # graph 0 as in a one-graph run
