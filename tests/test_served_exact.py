"""Served answers against the host heap reference, bit for bit.

Every route the serving stack answers a query by — the scheduler's p2p
``target=`` solve, a one-row batch, a four-row bucket, and
``shortest_paths(engine="auto")`` under the dispatch policy — must give
``ok`` / ``exact`` answers that equal ``core/host_ref.heap_dijkstra_f32``
bitwise, ``inf`` included.  That reference is a binary-heap Dijkstra in
f32 arithmetic over the incoming CSR arrays and shares no code with any
engine, so an agreement is an independent derivation of the answer.

Six graph families at n = 256 (the Table II shape m = 3n, directed,
disconnected, a 16 x 16 road grid, a skewed hub, and unit weights where
ties are everywhere), four seeds each: a seed draws both the graph and
the sources.  No landmarks, and no row cache on the scheduler routes, so
every answer comes from an engine solve.
"""
import functools

import numpy as np
import pytest

from repro.core import csr as C
from repro.core import graph as G
from repro.core.api import shortest_paths
from repro.core.host_ref import heap_dijkstra_f32
from repro.serve import DistanceCache, GraphRegistry, MicroBatchScheduler
from repro.serve.dispatch import DispatchPolicy, policy_override

N = 256
SEEDS = (0, 1, 2, 3)
# Dijkstra ranks of the p2p targets in the reference row (clamped to the
# reachable count): the nearest neighbour, two middle ranks, the farthest.
P2P_RANKS = (1, 8, 64, N - 1)


def _unit(seed):
    e, _ = G.random_edge_list(N, 3 * N, seed=seed)
    return C.csr_from_edge_list(N, e, np.ones(len(e), np.float32))


FAMILIES = {
    "random": lambda s: C.random_csr_graph(N, 3 * N, seed=s),
    "directed": lambda s: C.random_csr_graph(N, 3 * N, seed=s,
                                             directed=True),
    "disconnected": lambda s: C.random_csr_graph(N, 3 * N, seed=s,
                                                 connected=False),
    "road": lambda s: C.road_like_csr_graph(N, seed=s),
    "hub": lambda s: C.skewed_hub_csr_graph(N, seed=s),
    "unit": _unit,
}


@functools.lru_cache(maxsize=None)
def _graph(family, seed):
    return FAMILIES[family](seed)


@functools.lru_cache(maxsize=None)
def _ref(family, seed, source):
    cg = _graph(family, seed)
    return heap_dijkstra_f32(cg.indptr, cg.indices, cg.weights, cg.n,
                             source)


def _sources(seed, k):
    """``k`` distinct sources, drawn from the seed after the graph."""
    return [int(s) for s in
            np.random.default_rng([seed, 1]).choice(N, size=k,
                                                    replace=False)]


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


def _assert_row(got, ref, what):
    assert isinstance(got, np.ndarray) and got.dtype == np.float32, what
    assert got.shape == ref.shape, what
    assert np.array_equal(got, ref), what
    assert np.array_equal(_bits(got), _bits(ref)), what


def _scheduler(cg, max_batch):
    registry = GraphRegistry()
    sched = MicroBatchScheduler(registry, DistanceCache(capacity=0),
                                max_batch=max_batch)
    registry.register("g", cg, landmarks=0)
    return sched


def _served(answers, via):
    for a in answers:
        assert a.ok and a.exact and a.via == via, (a.query, a.via,
                                                   a.status, a.exact)
    return answers


def _p2p_targets(ref):
    order = np.argsort(ref, kind="stable")
    reached = int(np.isfinite(ref).sum())
    targets = [int(order[min(r, reached - 1)]) for r in P2P_RANKS
               if reached > 1]
    unreached = np.flatnonzero(~np.isfinite(ref))
    if unreached.size:
        targets.append(int(unreached[0]))
    return list(dict.fromkeys(targets))


def _route_p2p(family, seed):
    cg = _graph(family, seed)
    sched = _scheduler(cg, max_batch=4)
    (s,) = _sources(seed, 1)
    ref = _ref(family, seed, s)
    targets = _p2p_targets(ref)
    assert targets
    for t in targets:
        sched.submit("g", s, t)
        (a,) = _served(sched.tick(), "target")
        assert _bits(a.value) == _bits(ref[t]), (family, seed, s, t,
                                                 a.value, ref[t])


def _route_row1(family, seed):
    cg = _graph(family, seed)
    sched = _scheduler(cg, max_batch=4)
    (s,) = _sources(seed, 1)
    sched.submit("g", s)
    (a,) = _served(sched.tick(), "batch")
    _assert_row(a.value, _ref(family, seed, s), (family, seed, s))


def _route_rows4(family, seed):
    cg = _graph(family, seed)
    sched = _scheduler(cg, max_batch=4)
    sources = _sources(seed, 4)
    for s in sources:
        sched.submit("g", s)
    answers = _served(sched.tick(), "batch")
    assert sorted(a.query.source for a in answers) == sorted(sources)
    assert sched.engine_batches == 1
    for a in answers:
        s = a.query.source
        _assert_row(a.value, _ref(family, seed, s), (family, seed, s))


def _route_auto(family, seed):
    cg = _graph(family, seed)
    (s,) = _sources(seed, 1)
    policy = DispatchPolicy(delta_threshold=0)
    with policy_override(policy):
        res = shortest_paths(cg, s, engine="auto")
    want = ("delta_stepping" if policy.would_delta(cg, cg.n)
            else "frontier")
    assert res.engine == want, (family, seed, res.engine)
    _assert_row(np.asarray(res.dist), _ref(family, seed, s),
                (family, seed, s))


ROUTES = {"p2p": _route_p2p, "row1": _route_row1, "rows4": _route_rows4,
          "auto": _route_auto}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("route", list(ROUTES))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_served_answer_equals_heap_reference(family, route, seed):
    ROUTES[route](family, seed)
