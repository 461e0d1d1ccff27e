"""The benchmark's generated inputs: graphs and jobs from the seed."""
import hashlib
import json
import os

import numpy as np
import pytest
from scipy.sparse.csgraph import connected_components, dijkstra

from bench import graphs, traffic
from bench.tests.conftest import BENCH, ROOT, build

RANDOM = {"name": "r", "generator": "random_connected", "n": 500,
          "edges": 1500, "max_weight": 100.0}
P2P = {"graphs": 1, "sources": 6, "check_sources": 3, "trace_jobs": 2,
       "job": {"kind": "p2p", "rank_exponents": [1, 3, 5, 7]}}
ROWS = {"graphs": 1, "sources": 24, "check_sources": 4, "trace_jobs": 1,
        "job": {"kind": "rows", "sources_per_job": 4}}
BIG_SEED = 2 ** 31 + 77
KRON = {"name": "k", "generator": "kronecker", "scale": 12,
        "edgefactor": 16, "initiator": [0.57, 0.19, 0.19]}


def same_csr(a, b):
    return (a.n == b.n and np.array_equal(a.indptr, b.indptr)
            and np.array_equal(a.indices, b.indices)
            and a.weights.tobytes() == b.weights.tobytes())


@pytest.mark.parametrize("seed", [0, BIG_SEED])
def test_the_seed_fixes_the_graph(seed):
    assert same_csr(build(RANDOM, seed), build(RANDOM, seed))
    assert not same_csr(build(RANDOM, seed),
                        build(RANDOM, seed + 1))


@pytest.mark.parametrize("seed", [1, BIG_SEED, 2 ** 40 + 3])
def test_every_seed_gives_exactly_m_edges(seed):
    """The same sizes, so the same compiled programs, on every seed."""
    g = build(RANDOM, seed)
    assert g.arcs == 2 * RANDOM["edges"]
    assert g.indptr.shape == (RANDOM["n"] + 1,)


def test_graph_is_undirected_simple_and_connected():
    g = build(RANDOM, 3)
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
        build(dict(RANDOM, n=10, edges=46), 1)


@pytest.mark.parametrize("mix", [P2P, ROWS], ids=["p2p", "rows"])
def test_the_seed_fixes_the_jobs_and_their_sizes(mix):
    def jobs(seed):
        return traffic.make_jobs(mix, [build(RANDOM, seed)], seed)

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
    g = build(RANDOM, 4)
    warm, jobs = traffic.make_jobs(ROWS, [g], 4)
    assert len(jobs) == ROWS["sources"] // 4
    for j in warm + jobs:
        assert len(j.queries) == 4 and all(t is None for _, t in j.queries)
        assert len({s for s, _ in j.queries}) == 4


def test_rank_targets_match_scipy_dijkstra_rank():
    g = build(RANDOM, 4)
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
    g = build(RANDOM, 5)
    with pytest.raises(ValueError):
        traffic.make_jobs(dict(ROWS, sources=500), [g], 5)


def test_jobs_take_the_graphs_in_turn():
    seed = 2 ** 31 + 3
    gs = [build(RANDOM, seed, i) for i in range(3)]
    assert not same_csr(gs[0], gs[1]) and not same_csr(gs[1], gs[2])
    assert same_csr(gs[0], build(RANDOM, seed))
    warm, jobs = traffic.make_jobs(dict(ROWS, graphs=3), gs, seed)
    assert [w.graph for w in warm] == [0, 1, 2]
    assert [j.graph for j in jobs] == [0, 1, 2] * (ROWS["sources"] // 4)
    one, = traffic.make_jobs(ROWS, gs[:1], seed)[0]
    assert one == warm[0]                   # graph 0 as in a one-graph run


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def sha(b: bytes) -> str:
    return hashlib.sha256(b).hexdigest()


def jobs_digest(warm, jobs) -> str:
    return sha(json.dumps([[[list(q) for q in j.queries], j.label, j.graph]
                           for j in warm + jobs]).encode())


PINNED = {      # sha256 of the inputs the tableii-40k cells are measured
    # on: graphs 0 and 7 (indptr, indices, weights), then the p2p and the
    # batch mix's jobs
    0: [
        "5cc5be1840e99e17d5256c4f7c3360dbee0f081061a58ae016d4e134b9c0e04e",
        "c1c56343d6d0e03059a33c6c03bd285ed10cd77d92d69f256cf25949f8724e35",
        "caddf1380c9a14cf888f18f47a11a78d8d3385233d75dc39c21dda697c2643e0",
        "57def970b6376759f03df5d194165e740b6ac1a960ae7434b009a66ac7b3fb4d",
        "e5a5a5d151e60279b85135e45df5d8d4bd31417a8f149afe74ee1fcfdebda69a",
        "bf6e67b8a80bcf5302fbb01bd52bd4904dcfcb0a16e2ca7ed204c13ae0b88ffd",
        "7c47e6688b8523bce3e3c2516f6a4bfeb8ac11845430b428d9a08ec33fa46d2d",
        "6e5e42d09c29c0a0484e1f51d327a40d225146917b83fb3fecdf62f626a302c7"],
    2147483725: [
        "b5dd7df7459a7ae6f882e69a48405df4e54782b01d0ced748c8725a7ba14fc53",
        "d4e71f40eb3ac00dfee95da8fb2540a1c4d08e89a5b40c938df83335b208005f",
        "266380fcd7dac129ce0db45d1170c293ac5537b4afde6f14c7e764103d052f79",
        "bb5a09bdfaf41e74dc96037559002f3c87cbcca32b57f89545e3d17e4282649f",
        "3b5b4f69137cbc394bea81cdc6695ee6fa29af5f5d7fcf36ffb1b4dd65646b07",
        "93d6a0c754dc13d062be1d8724ff0fd25332adc1387c4818d57682f546353e6c",
        "8da36a7a96767e73f3fa30d8b6b16dd021eaefdeb91690afb2cf4f1a57ba72d2",
        "d48d3a76fbe0c96b9af474f94f87f3e1cc39f954bea280da9b3d526fd14042d8"],
    1099511627779: [
        "b1620c0d7aa605e28acb16e5fbe0b59d06afa473c0a7525a3d8d4d9ee9baed2d",
        "36bddea0010cc9113edf3fde0627acb52d2cc17ad762b9d0cfdd10761025c60c",
        "11cb01fe08fa5ece4a4494b818d58959aae7241a35fe3a7d93945c35818d7145",
        "c963b07ec1e526d4828c6be4894084cc351936809c9bc79c2164f68f8276bc4d",
        "3d061c6134814dc5273d6942469ce173ea5debb65d81785bdcc2eeb894e29bb2",
        "d4421c5e3cf0fc3b3486a9e77bfeacef150f2b956baf8d3633f214c9ff3af6b6",
        "648b142ebd21fef5de240c1d4a49b44f6e037f94e2e179362a3c051fdbdff532",
        "2c97c040c2f9ace21a913e55186d4a2832dfec8ec893ccaf4648ed8c2578ba62"],
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_the_tableii_inputs_are_pinned(seed):
    """The full-size tableii-40k graphs and both mixes' jobs, bit for bit
    as the cells' bounds were measured on."""
    config = load("configs", "tableii-40k.json")
    gs = [build(config, seed, g) for g in range(8)]
    got = [sha(a.tobytes()) for g in (0, 7)
           for a in (gs[g].indptr, gs[g].indices, gs[g].weights)]
    got.append(jobs_digest(*traffic.make_jobs(load("traffic", "p2p.json"),
                                              gs[:1], seed)))
    got.append(jobs_digest(*traffic.make_jobs(load("traffic", "batch.json"),
                                              gs, seed)))
    assert got == PINNED[seed]


def test_an_unknown_generator_names_the_files_there():
    with pytest.raises(ValueError, match="kronecker.*random_connected"):
        build(dict(RANDOM, generator="rmat"), 1)
    with pytest.raises(ValueError):
        build(dict(RANDOM, generator="../graphs/kronecker"), 1)


@pytest.mark.parametrize("seed", [0, BIG_SEED])
def test_the_seed_fixes_the_kronecker_graph(seed):
    assert same_csr(build(KRON, seed), build(KRON, seed))
    assert not same_csr(build(KRON, seed),
                        build(KRON, seed + 1))


def test_kronecker_quadrants_follow_the_initiator():
    """Every level's (row bit, column bit) falls in quadrant A, B, C, D
    with the initiator's odds, within 4 binomial sigmas."""
    e = graphs.generator("kronecker", ROOT).pairs(KRON, np.random.default_rng(7))
    m = KRON["edgefactor"] << KRON["scale"]
    assert e.shape == (m, 2) and e.min() >= 0 and e.max() < 2 ** KRON["scale"]
    a, b, c = KRON["initiator"]
    p = np.array([a, b, c, 1 - a - b - c])
    for level in range(KRON["scale"]):
        quad = 2 * ((e[:, 0] >> level) & 1) + ((e[:, 1] >> level) & 1)
        counts = np.bincount(quad, minlength=4)
        assert np.all(np.abs(counts - m * p) <= 4 * np.sqrt(m * p * (1 - p))),\
            (level, counts / m)


@pytest.mark.parametrize("seed", [3, BIG_SEED])
def test_the_kronecker_graph_is_symmetric_loop_free_and_skewed(seed):
    g = build(KRON, seed)
    ptr, dst, w = g.out_csr()
    src = np.repeat(np.arange(g.n), np.diff(ptr))
    fwd = {(int(u), int(v)): float(x) for u, v, x in zip(src, dst, w)}
    assert len(fwd) == g.arcs
    assert all(u != v for u, v in fwd)
    assert all(fwd[(v, u)] == x for (u, v), x in fwd.items())
    assert g.weights.dtype == np.float32
    assert np.all((g.weights >= 0) & (g.weights < 1))
    deg = np.diff(g.indptr)
    assert deg.max() >= 10 * deg.mean()
    assert 0 < np.mean(deg == 0) < 0.5     # hubs, and isolated vertices


def test_the_kronecker_labels_are_permuted():
    """Unpermuted, vertex 0 is the hub on every seed."""
    hubs = {int(np.argmax(np.diff(build(KRON, s).indptr)))
            for s in (1, 2)}
    assert len(hubs) == 2


@pytest.mark.parametrize("mix", [P2P, ROWS], ids=["p2p", "rows"])
def test_linked_sources_have_arcs(mix):
    g = build(KRON, 11)
    deg = np.diff(g.indptr)
    assert np.any(deg == 0)
    mix = dict(mix, sources=400 if mix is ROWS else 40)
    warm, jobs = traffic.make_jobs(mix, [g], 11)
    sources = [s for j in warm + jobs for s, _ in j.queries]
    assert len(set(sources)) == mix["sources"] + len(warm[0].queries)
    assert np.all(deg[sources] > 0)


@pytest.mark.parametrize("mix", [P2P, ROWS], ids=["p2p", "rows"])
def test_all_sources_is_the_uniform_draw(mix):
    """Where every vertex has an arc, sources are drawn as
    ``rng.choice(n)``, the draw the cells were measured on."""
    g = build(RANDOM, BIG_SEED)
    assert np.all(np.diff(g.indptr) > 0)
    warm, jobs = traffic.make_jobs(mix, [g], BIG_SEED)
    want = traffic.rng_for(BIG_SEED).choice(
        g.n, size=mix["sources"] + len(warm[0].queries), replace=False)
    drawn = [s for j in warm + jobs for s, _ in j.queries]
    assert list(dict.fromkeys(drawn)) == want.tolist()


def test_too_many_linked_sources_are_refused():
    g = build(dict(KRON, scale=6, edgefactor=1), 2)
    linked = np.count_nonzero(np.diff(g.indptr))
    assert linked < g.n
    with pytest.raises(ValueError, match=f"of {linked} vertices"):
        traffic.make_jobs(dict(ROWS, sources=4 * (linked // 4)), [g], 2)
