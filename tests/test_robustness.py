"""Serving fault tolerance: typed failures, deadlines, degradation, chaos.

The robustness layer's contract (README.md §Robustness): a bad query or a
lost graph fails ITS caller/answer with a typed status — never the tick
serving everyone else; deadline pressure sheds or degrades rather than
queueing without bound; a capped solve surfaces ``not_converged`` instead
of serving non-fixpoint labels; injected faults (serve/faults.py) are
deterministic, so every chaos replay is reproducible byte for byte.  The
bitwise-exactness invariant of tests/test_serve.py binds exactly the
answers that still claim ``exact=True``.
"""
import numpy as np
import pytest

from repro.core import csr as C
from repro.core._axes import make_mesh
from repro.core.api import shortest_paths
from repro.dynamic import DynamicGraph
from repro.serve import (DistanceCache, FaultPlan, GraphRegistry,
                         MicroBatchScheduler, QueryRejected,
                         SchedulerStalled)


def _stack(cg, *, name="g", landmarks=0, **kw):
    registry = GraphRegistry()
    cache = DistanceCache(capacity=kw.pop("cache_rows", 64))
    sched = MicroBatchScheduler(registry, cache, max_batch=8, **kw)
    if cg is not None:
        registry.register(name, cg, landmarks=landmarks)
    return registry, cache, sched


def _serial(g, s):
    return shortest_paths(g, s, engine="serial").dist


# ---------------------------------------------------------------------------
# eager submit validation
# ---------------------------------------------------------------------------

def test_submit_validation_rejects_malformed_queries_eagerly():
    cg = C.random_csr_graph(50, 150, seed=0)
    _, _, sched = _stack(cg)
    bad = [
        dict(graph=3, source=0),                  # graph name not a str
        dict(graph="g", source=True),             # bool is not a vertex
        dict(graph="g", source=1.5),              # non-integral source
        dict(graph="g", source=-1),               # negative source
        dict(graph="g", source=50),               # >= n for registered g
        dict(graph="g", source=0, target=-2),     # negative target
        dict(graph="g", source=0, target=99),     # >= n target
    ]
    for kw in bad:
        with pytest.raises(QueryRejected):
            sched.submit(**kw)
    with pytest.raises(QueryRejected):
        sched.submit("g", 0, deadline=float("nan"))
    assert sched.pending == 0                     # nothing was admitted
    assert sched.stats()["submissions_rejected"] == len(bad) + 1
    # the rejection failed only its caller: the scheduler still serves
    sched.submit("g", 3)
    (a,) = sched.drain()
    assert a.ok and a.exact and np.array_equal(a.value, _serial(cg, 3))


def test_submit_unregistered_graph_is_answered_graph_gone_at_tick():
    # an unknown name is NOT an eager rejection (it may be registered
    # before the tick); unresolved, it fails as a typed answer instead
    _, _, sched = _stack(None)
    q = sched.submit("ghost", 2)
    (a,) = sched.tick()
    assert a.query is q and a.status == "graph_gone"
    assert not a.ok and not a.exact and a.value is None


# ---------------------------------------------------------------------------
# evicted-graph race (single device; the sharded twin lives in
# tests/test_serve_sharded.py)
# ---------------------------------------------------------------------------

def test_evicted_graph_race_fails_typed_while_live_graph_serves():
    g0 = C.random_csr_graph(120, 360, seed=1)
    g1 = C.random_csr_graph(120, 360, seed=2)
    registry, _, sched = _stack(g0, name="g0")
    registry.register("g1", g1)
    sched.submit("g0", 5)                         # admitted while g0 lives
    sched.submit("g1", 7)
    registry.evict("g0")                          # race: evicted pre-tick
    answers = {a.query.graph: a for a in sched.tick()}
    assert answers["g0"].status == "graph_gone" and not answers["g0"].ok
    assert answers["g1"].status == "ok" and answers["g1"].exact
    assert np.array_equal(answers["g1"].value, _serial(g1, 7))
    assert registry.evict("g0") is None           # idempotent


# ---------------------------------------------------------------------------
# deadlines, bounded queue, shedding
# ---------------------------------------------------------------------------

def test_expired_query_answered_deadline_exceeded_before_solving():
    cg = C.random_csr_graph(60, 180, seed=3)
    _, _, sched = _stack(cg)
    sched.submit("g", 4, arrival=0.0, deadline=1.0)
    sched.submit("g", 9, arrival=0.0)             # no deadline: must serve
    by_src = {a.query.source: a for a in sched.tick(now=2.0)}
    assert by_src[4].status == "deadline_exceeded" and by_src[4].value is None
    assert by_src[9].ok and np.array_equal(by_src[9].value, _serial(cg, 9))
    assert sched.stats()["deadline_expired"] == 1


def test_bounded_queue_rejects_p2p_and_sheds_for_full_rows():
    cg = C.random_csr_graph(60, 180, seed=4)
    _, _, sched = _stack(cg, max_queue=2)
    sched.submit("g", 1, 2)
    sched.submit("g", 3, 4)
    # saturated + p2p newcomer: rejected at the submit boundary
    with pytest.raises(QueryRejected):
        sched.submit("g", 5, 6)
    # saturated + full-row newcomer: the NEWEST queued p2p (cheapest to
    # recompute — a bounded early-exit solve, never cached) is shed for it
    q = sched.submit("g", 7)
    assert sched.pending == 2
    answers = sched.drain()
    shed = [a for a in answers if a.status == "rejected"]
    assert len(shed) == 1 and shed[0].query.source == 3
    served = {a.query.source: a for a in answers if a.ok}
    assert set(served) == {1, 7} and served[7].query is q
    st = sched.stats()
    assert st["shed"] == 1 and st["submissions_rejected"] == 1


# ---------------------------------------------------------------------------
# graceful degradation under deadline pressure
# ---------------------------------------------------------------------------

def test_p2p_degrades_to_landmark_bracket_under_pressure():
    cg = C.sparse_csr_graph(200, seed=5)
    registry, _, sched = _stack(cg, landmarks=4, degrade_margin=0.5)
    ids = set(int(i) for i in registry.get("g").landmarks_ready().ids)
    src = next(v for v in range(cg.n) if v not in ids)
    tgt = next(v for v in range(cg.n - 1, -1, -1)
               if v not in ids and v != src)      # neither endpoint exact
    sched.submit("g", src, tgt, deadline=1.0)
    (a,) = sched.drain(now=0.8)                   # 0.2s left <= margin
    assert a.via == "degraded" and a.status == "ok" and not a.exact
    lb, ub = a.bounds
    true = float(_serial(cg, src)[tgt])
    assert lb <= true <= ub and a.value == ub     # ub is a real path
    assert sched.stats()["degraded_p2p"] == 1


def test_full_row_degrades_to_stale_version_under_pressure():
    cg = C.random_csr_graph(100, 300, seed=6)
    dyn = DynamicGraph(cg, overlay_capacity=16)
    registry, cache, sched = _stack(dyn, degrade_margin=0.5, repair_rows=0)
    sched.submit("g", 8)
    (fresh,) = sched.drain()
    v0_row = np.asarray(fresh.value).copy()
    # bump a TIGHT edge of row 8 (one the row's shortest paths use), so
    # the row is genuinely affected; repair_rows=0 means it cannot be
    # repaired, so the degrade-enabled scheduler retains it as STALE
    us = np.asarray(dyn.base.indices)
    vs = np.asarray(dyn.base.dst_ids())
    u, v = next(
        (int(a), int(b)) for a, b in zip(us, vs)
        if np.isfinite(v0_row[a])
        and np.float32(v0_row[a] + dyn.weight_of(a, b)) == v0_row[b])
    registry.mutate("g", [("update", u, v,
                           float(dyn.weight_of(u, v)) + 50.0)])
    assert sched.rows_staled >= 1
    sched.submit("g", 8, deadline=1.0)
    (a,) = sched.drain(now=0.9)
    assert a.via == "degraded" and a.status == "ok" and not a.exact
    assert np.array_equal(a.value, v0_row)        # the versioned stale row
    assert sched.stats()["degraded_batch"] == 1
    # without pressure the same query re-solves exactly at the new version
    sched.submit("g", 8)
    (b,) = sched.drain()
    assert b.exact and np.array_equal(b.value, _serial(dyn.snapshot(), 8))


# ---------------------------------------------------------------------------
# retries, backoff, typed solve failures
# ---------------------------------------------------------------------------

def test_transient_fault_is_retried_to_a_bitwise_exact_answer():
    cg = C.random_csr_graph(80, 240, seed=7)
    plan = FaultPlan(seed=1, rates={"solve": 1.0}, max_per_site=1)
    _, _, sched = _stack(cg, faults=plan, retry_budget=2)
    sched.submit("g", 6)
    (a,) = sched.drain()
    assert a.ok and a.exact and np.array_equal(a.value, _serial(cg, 6))
    st = sched.stats()
    assert st["solve_exceptions"] == 1 and st["retries"] == 1
    assert plan.counts()["solve"] == 1


def test_persistent_fault_exhausts_retry_budget_to_solve_failed():
    cg = C.random_csr_graph(80, 240, seed=8)
    plan = FaultPlan(seed=2, rates={"solve": 1.0})    # never recovers
    _, _, sched = _stack(cg, faults=plan, retry_budget=2)
    sched.submit("g", 6)
    answers = sched.drain()                       # guard must NOT trip:
    (a,) = answers                                # backoff ticks progress
    assert a.status == "solve_failed" and not a.ok and a.value is None
    assert a.query.attempts == 3                  # 1 try + 2 retries
    assert sched.stats()["retries"] == 2


def test_clip_fault_surfaces_not_converged_and_caches_nothing():
    cg = C.sparse_csr_graph(150, seed=9)          # diameter >> 1 sweep
    plan = FaultPlan(seed=3, rates={"clip": 1.0}, clip_sweeps=1)
    _, cache, sched = _stack(cg, faults=plan)
    sched.submit("g", 0)
    sched.submit("g", 0, 140)
    answers = sched.drain()
    assert len(answers) == 2
    assert all(a.status == "not_converged" and not a.ok for a in answers)
    assert len(cache) == 0                        # capped labels never enter
    assert sched.stats()["not_converged"] == 2


def test_poisoned_mutation_batch_rolls_back_atomically():
    cg = C.random_csr_graph(90, 270, seed=10)
    dyn = DynamicGraph(cg, overlay_capacity=16)
    plan = FaultPlan(seed=4, rates={"mutate": 1.0}, max_per_site=1)
    registry, _, sched = _stack(dyn, faults=plan)
    u, v = int(dyn.base.indices[0]), int(dyn.base.dst_ids()[0])
    w0 = float(dyn.weight_of(u, v))
    sched.submit_mutation("g", "update", u, v, w0 + 5.0)
    acks = sched.tick()
    assert len(acks) == 1 and acks[0].status == "rejected"
    assert dyn.version == 0 and float(dyn.weight_of(u, v)) == w0
    # the graph is untouched: a fresh query is exact against the base
    sched.submit("g", 12)
    (a,) = sched.drain()
    assert a.exact and np.array_equal(a.value, _serial(cg, 12))


# ---------------------------------------------------------------------------
# drain progress guard
# ---------------------------------------------------------------------------

def test_drain_raises_stalled_instead_of_spinning_forever():
    cg = C.random_csr_graph(40, 120, seed=11)
    _, _, sched = _stack(cg)
    # simulate the requeue-path regression the guard exists for: a solve
    # that silently answers nobody (no exception, no retry, no answer)
    sched._solve_batch = lambda handle, queries: []
    sched.submit("g", 2)
    with pytest.raises(SchedulerStalled):
        sched.drain()


# ---------------------------------------------------------------------------
# chaos determinism
# ---------------------------------------------------------------------------

def test_fault_plan_schedule_is_a_pure_function_of_seed():
    mk = lambda: FaultPlan(seed=42, rates={"solve": 0.5, "clip": 0.3},
                           max_per_site=3)
    a, b = mk(), mk()
    fires = [(s, a.roll(s), b.roll(s))
             for s in ("solve", "clip", "solve", "evict") * 20]
    assert all(x == y for _, x, y in fires)
    assert a.counts() == b.counts()
    assert a.counts()["solve"] <= 3               # cap respected
    assert a.probes["solve"] == b.probes["solve"] == 40


def test_chaos_replay_statuses_are_deterministic():
    def once():
        cg = C.random_csr_graph(70, 210, seed=12)
        plan = FaultPlan(seed=9,
                         rates={"solve": 0.4, "clip": 0.4}, max_per_site=2)
        _, _, sched = _stack(cg, faults=plan, retry_budget=1)
        for s in (3, 9, 3, 40, 41, 42):
            sched.submit("g", s)
        sched.submit("g", 5, 60)
        return [(a.query.qid, a.status, a.exact) for a in sched.drain()]

    assert once() == once()


# ---------------------------------------------------------------------------
# solver guardrails: max_sweeps= and the converged flag
# ---------------------------------------------------------------------------

def _path_graph(n):
    import repro.core.graph as G
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], 1)
    return G.csr_from_edge_list(n, edges, np.ones(n - 1))


@pytest.mark.parametrize("engine", ["bellman_csr", "frontier",
                                    "multisource_csr"])
def test_max_sweeps_cap_reports_not_converged(engine):
    cg = _path_graph(12)                          # needs ~11 sweeps from 0
    src = [0] if engine == "multisource_csr" else 0
    capped = shortest_paths(cg, src, engine=engine, max_sweeps=2)
    assert capped.converged is False and capped.sweeps == 2
    free = shortest_paths(cg, src, engine=engine)
    assert free.converged is True
    dist = free.dist[0] if engine == "multisource_csr" else free.dist
    assert np.array_equal(dist, np.arange(12, dtype=np.float32))


@pytest.mark.parametrize("engine", ["bellman_csr_sharded",
                                    "frontier_sharded",
                                    "multisource_csr_sharded"])
def test_sharded_max_sweeps_cap_reports_not_converged(engine):
    mesh = make_mesh((1,), ("data",))
    cg = _path_graph(16)
    src = [0] if engine == "multisource_csr_sharded" else 0
    capped = shortest_paths(cg, src, engine=engine, mesh=mesh,
                            max_sweeps=2)
    assert capped.converged is False
    free = shortest_paths(cg, src, engine=engine, mesh=mesh)
    assert free.converged is True
    dist = free.dist[0] if engine == "multisource_csr_sharded" else free.dist
    assert np.array_equal(dist, np.arange(16, dtype=np.float32))
