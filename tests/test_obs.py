"""Observability layer invariants (repro/obs + its serve integration).

Pins the contracts ISSUE 9 introduced:

- metrics: get-or-create series, label qualification, kind-mismatch
  errors, deterministic sorted snapshots;
- tracing: span nesting/ordering under an injected clock, Chrome-trace
  schema validity, and the disabled-mode guarantee — a NullTracer run
  produces bitwise-identical scheduler answers and records nothing;
- unification: every legacy ``stats()`` count of the cache / registry /
  scheduler equals its series in the merged ``snapshot()`` (no counter
  lost or renamed by the migration);
- determinism: two same-seed replays on fresh stacks produce identical
  metric snapshots (including under a seeded fault plan);
- jit-retrace accounting: repeat scheduler ticks after warmup, and
  repeat DynamicGraph mutate+query cycles after warmup, add ZERO new
  traces of any engine (``jit.retrace{fn=...}`` is flat);
- latency split: queue-wait vs service-time are separated and both
  percentiles reported;
- cost records: the core.api shim emits schema-valid per-solve records;
- answer chains: a traced replay's submit → tick → solve → answer chain
  reconstructs for every exact engine-served answer.
"""
import numpy as np
import pytest

from repro.core import csr as C
from repro.core.api import shortest_paths
from repro.obs import (CostLog, MetricsRegistry, Tracer, set_cost_log,
                       set_tracer)
from repro.obs.metrics import default_registry
from repro.obs.validate import (reconstruct_answer_chains,
                                validate_chrome_trace,
                                validate_cost_records)
from repro.serve import (DistanceCache, GraphRegistry, LatencyRecorder,
                         MicroBatchScheduler, make_trace)


def _stack(cg, *, landmarks=0, name="g", **kw):
    registry = GraphRegistry()
    cache = DistanceCache(capacity=64)
    sched = MicroBatchScheduler(registry, cache, max_batch=8, **kw)
    registry.register(name, cg, landmarks=landmarks)
    return sched


# ---------------------------------------------------------------- metrics


def test_metrics_registry_series():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    c.inc()
    c.inc(2)
    assert reg.counter("hits") is c and c.value == 3
    g = reg.gauge("rows", fn=lambda: 7)
    assert g.value == 7
    # labeled series are distinct and qualify deterministically
    a = reg.counter("answered", via="batch")
    b = reg.counter("answered", via="cache")
    a.inc(5)
    b.inc(1)
    snap = reg.snapshot()
    assert snap["answered{via=batch}"] == 5
    assert snap["answered{via=cache}"] == 1
    assert snap["hits"] == 3 and snap["rows"] == 7
    assert list(snap) == sorted(snap)
    with pytest.raises(TypeError):
        reg.gauge("hits")                  # kind mismatch


def test_span_nesting_under_injected_clock():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    tr = Tracer(clock=clock)
    with tr.span("tick", tick=1) as sp:          # t0=1
        with tr.span("batch_solve", qids=(7,)):  # t0=2, t1=3
            pass
        sp.set(answers=1)
    # inner closed first, outer second; depths record nesting
    inner, outer = tr.spans
    assert (inner.name, outer.name) == ("batch_solve", "tick")
    assert inner.depth == 1 and outer.depth == 0
    assert (inner.t0, inner.t1) == (2.0, 3.0)
    assert (outer.t0, outer.t1) == (1.0, 4.0)
    assert outer.args == {"tick": 1, "answers": 1}
    doc = tr.to_chrome()
    assert validate_chrome_trace(doc) == []
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts)


def test_chrome_schema_rejects_malformed():
    assert validate_chrome_trace({}) == ["missing top-level traceEvents"]
    bad = {"traceEvents": [{"ph": "X", "name": "tick", "ts": 1.0,
                            "pid": 1, "tid": 1}]}       # no dur
    assert any("dur" in e for e in validate_chrome_trace(bad))
    bad = {"traceEvents": [{"ph": "?", "name": "x", "ts": 0.0,
                            "pid": 1, "tid": 1}]}
    assert any("unsupported ph" in e for e in validate_chrome_trace(bad))


# ---------------------------------------------------------------- tracing


def _replay(cg, *, seed=3, queries=24, landmarks=0):
    sched = _stack(cg, landmarks=landmarks)
    trace = make_trace("zipf", [("g", cg.n)], num_queries=queries,
                       rate=1000.0, seed=seed, hot_seed=5)
    for e in trace:
        sched.submit("g", e.source, e.target, arrival=e.arrival)
    return sched, sched.drain(0.0)


def test_disabled_tracing_is_noop_and_answers_identical():
    cg = C.random_csr_graph(96, 288, seed=1)
    _, base = _replay(cg)                       # NULL_TRACER default
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        _, traced = _replay(cg)
    finally:
        set_tracer(prev)
    assert len(base) == len(traced) and len(tr.spans) > 0
    for a, b in zip(base, traced):
        assert a.query.qid == b.query.qid and a.via == b.via
        assert np.array_equal(np.asarray(a.value), np.asarray(b.value))
    # and the disabled side really recorded nothing
    _, again = _replay(cg)
    assert len(again) == len(base)


def test_answer_chains_reconstruct_from_traced_replay():
    cg = C.random_csr_graph(96, 288, seed=1)
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        _replay(cg)
    finally:
        set_tracer(prev)
    doc = tr.to_chrome()
    assert validate_chrome_trace(doc) == []
    assert reconstruct_answer_chains(doc) == []
    # drop the submit instants: every exact engine answer must now fail
    doc["traceEvents"] = [e for e in doc["traceEvents"]
                          if e.get("name") != "submit"]
    errs = reconstruct_answer_chains(doc)
    assert errs and all("no submit instant" in e for e in errs)


# ------------------------------------------------------------ unification


def test_stats_unification_nothing_lost():
    cg = C.random_csr_graph(96, 288, seed=2)
    sched, answers = _replay(cg, landmarks=4)
    assert answers
    snap = sched.snapshot()
    s = sched.stats()
    for key in ("ticks", "engine_batches", "engine_sources",
                "target_solves", "dedup_saved", "rows_kept",
                "rows_repaired", "rows_invalidated", "rows_staled",
                "repair_edges", "submissions_rejected", "shed",
                "deadline_expired", "degraded_p2p", "degraded_batch",
                "solve_exceptions", "retries", "not_converged",
                "sharded_batches", "sharded_p2p", "sharded_sources",
                "sharded_edges"):
        assert snap[f"sched.{key}"] == s[key], key
    for via, count in s["answered_via"].items():
        assert snap.get(f"sched.answered{{via={via}}}", 0) == count, via
    c = s["cache"]
    assert snap["cache.hits"] == c["hits"]
    assert snap["cache.misses"] == c["misses"]
    assert snap["cache.evictions"] == c["evictions"]
    assert snap["cache.rows"] == c["rows"]
    r = s["registry"]
    assert snap["registry.graphs"] == r["graphs"]
    assert snap["registry.registered"] == r["registered"]
    assert snap["registry.evicted"] == r["evicted"]
    assert snap["registry.mutations"] == r["mutations"]
    assert snap["registry.edges_mutated"] == r["edges_mutated"]
    # legacy attribute reads still resolve (back-compat shim)
    assert sched.ticks == s["ticks"]
    assert sched.dedup_saved == s["dedup_saved"]
    assert sched.cache.hits == c["hits"]
    assert sched.registry.registered == r["registered"]


def test_snapshot_deterministic_under_seeded_replay():
    cg = C.random_csr_graph(96, 288, seed=4)
    s1, _ = _replay(cg, seed=9)
    s2, _ = _replay(cg, seed=9)
    assert s1.snapshot() == s2.snapshot()


def test_snapshot_deterministic_under_seeded_chaos():
    from repro.serve import FaultPlan

    cg = C.random_csr_graph(96, 288, seed=4)
    snaps = []
    for _ in range(2):
        plan = FaultPlan(seed=11, rates={"solve": 0.3, "clip": 0.2})
        sched = _stack(cg, faults=plan, retry_budget=2)
        trace = make_trace("zipf", [("g", cg.n)], num_queries=24,
                           rate=1000.0, seed=9, hot_seed=5)
        for e in trace:
            sched.submit("g", e.source, e.target, arrival=e.arrival)
        sched.drain(0.0)
        snaps.append(sched.snapshot())
    assert snaps[0] == snaps[1]


# ------------------------------------------------------------ jit retrace


def _total_retraces() -> int:
    return sum(s.value for s in default_registry().find("jit.retrace"))


def test_zero_retraces_across_repeat_ticks():
    cg = C.random_csr_graph(80, 240, seed=6)
    sched = _stack(cg)
    # warmup wave compiles every (engine, bucket) this workload hits
    for src in (0, 1):
        sched.submit("g", src, arrival=0.0)
    sched.drain(0.0)
    before = _total_retraces()
    for wave in range(1, 4):
        for src in (2 * wave, 2 * wave + 1):    # same shape, new sources
            sched.submit("g", src, arrival=0.0)
        sched.drain(0.0)
    assert _total_retraces() == before, (
        "repeat scheduler ticks retraced a jitted engine")


def test_zero_retraces_across_dynamic_versions():
    from repro.dynamic import DynamicGraph

    cg = C.random_csr_graph(80, 240, seed=6)
    dyn = DynamicGraph(cg, overlay_capacity=64)
    sched = _stack(dyn, name="d")
    # two warm cycles: version v solves, then a mutation commits v+1 and
    # the repair + re-solve paths compile for the overlay shape
    for warm in range(2):
        sched.submit_mutation("d", "add", 3 + warm, 60 + warm, 1.5,
                              arrival=0.0)
        sched.submit("d", warm, arrival=0.0)
        sched.drain(0.0)
    before = _total_retraces()
    v0 = dyn.version
    for wave in range(3):
        sched.submit_mutation("d", "add", 10 + wave, 50 + wave, 2.0,
                              arrival=0.0)
        sched.submit("d", 2 + wave, arrival=0.0)
        sched.drain(0.0)
    assert dyn.version > v0                     # versions really advanced
    assert _total_retraces() == before, (
        "DynamicGraph version changes retraced a jitted engine")


# ---------------------------------------------------------- latency split


def test_latency_recorder_splits_queue_and_service():
    cg = C.random_csr_graph(64, 192, seed=7)
    sched = _stack(cg)
    sched.submit("g", 0, arrival=0.0)
    sched.submit("g", 1, arrival=0.5)
    answers = sched.drain(2.0)                  # served at now=2.0
    rec = LatencyRecorder()
    for a in answers:
        assert a.service_start == 2.0
        a.done_at = 3.0
        rec.observe(a, a.done_at)
    lat = rec.summary()
    # queue = service_start - arrival (2000 and 1500 ms here); service =
    # done - service_start.  np.percentile interpolates between the two.
    assert lat["queue_p99_ms"] == pytest.approx(1995.0)
    assert lat["queue_p50_ms"] == pytest.approx(1750.0)
    assert lat["service_p50_ms"] == pytest.approx(1000.0)
    assert lat["service_p99_ms"] == pytest.approx(1000.0)
    # total latency keeps its original meaning: done - arrival
    # (3000 and 2500 ms, interpolated the same way)
    assert lat["p99_ms"] == pytest.approx(2995.0)


# ----------------------------------------------------------- cost records


def test_api_shim_emits_schema_valid_cost_records():
    cg = C.random_csr_graph(64, 192, seed=8)
    cl = CostLog()
    prev = set_cost_log(cl)
    try:
        res = shortest_paths(cg, 0, engine="frontier")
    finally:
        set_cost_log(prev)
    assert len(cl.records) == 1
    r = cl.records[0]
    assert r.engine == "frontier" and r.n == cg.n and r.m == cg.nnz
    assert r.sweeps == res.sweeps
    assert r.edges_relaxed == res.edges_relaxed
    assert r.wall_ms > 0 and r.converged
    assert validate_cost_records([r.to_dict()]) == []
    # disabled log: nothing recorded, result identical
    res2 = shortest_paths(cg, 0, engine="frontier")
    assert np.array_equal(res.dist, res2.dist)
    assert len(cl.records) == 1
