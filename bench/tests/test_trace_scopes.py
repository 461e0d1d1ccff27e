"""Device time named by the engines' stages, and idle time named by the
program's spans (``bench/trace_scopes.py``), pinned on two traces recorded
on a TPU v5e:

- ``batch_v5e.xplane.pb``: one Table II batch job (n = 2^20, S = 4), from a
  program with neither ``sssp.*`` annotations nor named stages;
- ``p2p_v5e.xplane.pb``: two low-rank p2p queries of the ``tableii-40k.p2p``
  cell, with both.
"""
import importlib.util
import os
import types

import pytest

from bench import harness, trace_reduce, trace_scopes
from bench.tests.conftest import ROOT

DATA = os.path.join(os.path.dirname(__file__), "data")
BATCH = os.path.join(DATA, "batch_v5e.xplane.pb")
P2P = os.path.join(DATA, "p2p_v5e.xplane.pb")


@pytest.fixture(scope="module", params=[BATCH, P2P], ids=["batch", "p2p"])
def both(request):
    return (request.param, trace_reduce.reduce(request.param),
            trace_scopes.reduce(request.param))


@pytest.fixture(scope="module")
def p2p():
    return trace_reduce.reduce(P2P), trace_scopes.reduce(P2P)


def _tpu_plane(path):
    (plane,) = [p for p in trace_scopes.read_planes(path)
                if p["name"] == "/device:TPU:0"]
    return plane


def test_tf_op_is_read_from_the_event_metadata():
    plane = _tpu_plane(BATCH)
    assert len(plane["tf_op"]) == 10
    assert plane["tf_op"][6] == "jit(sssp_multisource_csr)/broadcast_in_dim:"
    assert all(v.startswith("jit(") for v in plane["tf_op"].values())
    ops = _tpu_plane(P2P)["tf_op"].values()
    assert any("/frontier.relax/" in v for v in ops)


def _xplane_pb2():
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or spec.origin is None:
        return None
    path = os.path.join(os.path.dirname(spec.origin), "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    if not os.path.exists(path):
        return None
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)        # google.protobuf only
    return mod


@pytest.mark.parametrize("path", [BATCH, P2P], ids=["batch", "p2p"])
def test_the_decoder_agrees_with_the_xplane_proto(path):
    pb2 = _xplane_pb2()
    if pb2 is None:
        pytest.skip("no xplane_pb2 to cross-check against")
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ours = {p["name"]: p for p in trace_scopes.read_planes(path)}
    for plane in space.planes:
        stat = {k: v.name for k, v in plane.stat_metadata.items()}
        tf_op = {}
        for mid, md in plane.event_metadata.items():
            for st in md.stats:
                if stat.get(st.metadata_id) == "tf_op":
                    tf_op[mid] = st.str_value or stat.get(st.ref_value)
        mine = ours[plane.name]
        assert mine["tf_op"] == tf_op, plane.name
        assert mine["names"] == {k: v.name
                                 for k, v in plane.event_metadata.items()}
    dev = [p for p in space.planes if p.name == "/device:TPU:0"][0]
    (line,) = [ln for ln in dev.lines if ln.name == "XLA Ops"]
    events = trace_scopes.read_planes(
        path, lambda p: (lambda ln, ev: ln == "XLA Ops")
        if p == "/device:TPU:0" else None)
    (mine,) = [p for p in events if p["name"] == "/device:TPU:0"]
    want = [(line.timestamp_ns + ev.offset_ps / 1e3, ev.duration_ps / 1e3,
             ev.metadata_id) for ev in line.events]
    assert [(s, e - s, m) for s, e, m, _ in mine["events"]["XLA Ops"]] == [
        (s, pytest.approx(d, abs=1e-3), m) for s, d, m in want]


def test_idle_by_span_sums_to_the_idle_time(both):
    _, reduced, scopes = both
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in scopes["idle_by_span"]) == pytest.approx(
        idle, rel=1e-3)
    busy = sum(s for _, s in scopes["device_by_scope"])
    ops = sum(s for _, s in reduced["device_ops"])
    # the same leaf ops (ProfileData rounds each to the ns)
    assert busy == pytest.approx(ops, rel=1e-3)


def test_a_program_without_spans_or_stages_reads_none_and_other():
    scopes = trace_scopes.reduce(BATCH)
    assert [k for k, _ in scopes["idle_by_span"]] == ["none"]
    assert [k for k, _ in scopes["device_by_scope"]] == ["other"]
    assert scopes["ticks"] == 0 and scopes["tick_idle_s"] == 0.0
    assert trace_scopes.readings(scopes, []) == {}


def test_device_time_is_named_by_the_frontier_stages(p2p):
    _, scopes = p2p
    by = dict(scopes["device_by_scope"])
    for stage in ("frontier.compact", "frontier.relax", "frontier.test"):
        assert by.get(stage, 0) > 0, stage


def _idle_in_bench_ticks(path):
    """Idle time of the window inside ``bench.tick``, by exact
    intersection, from ``ProfileData`` alone."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for ev in ln.events
            if ev.name.startswith("bench.")]
    (w0, w1), = [(s, e) for n, s, e in host if n == "bench.window"]
    busy = sorted((max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1))
                  for p in pd.planes if p.name == "/device:TPU:0"
                  for ln in p.lines if ln.name == "XLA Ops"
                  for ev in ln.events
                  if trace_reduce.op_parts(ev.name)[1] not in
                  trace_reduce.CONTROL)
    idle = 0.0
    for _, t0, t1 in (h for h in host if h[0] == "bench.tick"):
        covered, cur = 0.0, t0
        for s, e in busy:
            s, e = max(s, cur), min(e, t1)
            if e > s:
                covered += e - s
                cur = e
        idle += (t1 - t0) - covered
    return idle / 1e9


def test_tick_idle_is_split_among_the_program_spans(p2p):
    reduced, scopes = p2p
    split = dict(scopes["tick_idle_by_span"])
    assert sum(split.values()) == pytest.approx(_idle_in_bench_ticks(P2P),
                                                rel=0.01)
    # trace_reduce gives each gap whole to the annotation over its
    # midpoint, so its bench.tick idle also holds what spills past a tick
    bench_tick = dict(reduced["idle_by_host"])["bench.tick"]
    assert sum(split.values()) == pytest.approx(bench_tick, rel=0.03)
    assert {"sssp.fetch", "sssp.tick"} <= set(split)
    assert set(split) <= {"none"} | {
        "sssp." + s for s in ("tick", "p2p_solve", "stage", "launch",
                              "wait", "fetch")}
    assert scopes["ticks"] == 2
    inside = scopes["tick_idle_s"]
    assert inside == pytest.approx(
        sum(v for k, v in split.items() if k != "none"), rel=1e-6)


def test_device_events_are_moved_onto_the_host_clock(p2p):
    _, scopes = p2p
    ((shift, low, high),) = scopes["clock_offset_ms"]
    # the v5e trace puts a program's first op ~1.5 ms before the host
    # starts to enqueue it
    assert 1.0 < low <= shift <= high < 2.5
    assert shift == pytest.approx((low + high) / 2)
    plane = {"events": {"XLA Modules": [(100.0, 200.0, 1, {"_c": 7})]}}
    host = [("DoEnqueueProgram", 150.0, 160.0, {"_p": 7}),
            ("CompleteCallbacks", 230.0, 240.0, {"_c": 7})]
    assert trace_scopes.clock_offset(plane, host) == (40.0, 50.0, 30.0)
    assert trace_scopes.clock_offset(plane, host[:1]) == (0.0, 50.0, None)


def test_readings_on_the_recorded_p2p_trace(p2p):
    _, scopes = p2p
    by = dict(scopes["device_by_scope"])
    spans = [types.SimpleNamespace(name="p2p_solve", args={
        "sweeps": 10, "edges_relaxed": 50_000})] * 2
    r = trace_scopes.readings(scopes, spans)
    assert r["tick_idle_ms"] == pytest.approx(
        scopes["tick_idle_s"] / 2 * 1e3)
    assert r["compact_ms.p2p"] == pytest.approx(
        by["frontier.compact"] / 20 * 1e3)
    assert r["relax_ns_per_edge.p2p"] == pytest.approx(
        by["frontier.relax"] / 100_000 * 1e9)


# -- intervals -----------------------------------------------------------------

def test_innermost_names_each_piece_by_the_deepest_span():
    anns = [("sssp.tick", 0, 100), ("sssp.p2p_solve", 10, 60),
            ("sssp.launch", 10, 20), ("sssp.wait", 20, 55),
            ("sssp.fetch", 55, 60), ("sssp.fetch", 70, 80)]
    assert trace_scopes._innermost(anns) == [
        (0, 10, "sssp.tick"), (10, 20, "sssp.launch"),
        (20, 55, "sssp.wait"), (55, 60, "sssp.fetch"),
        (60, 70, "sssp.tick"), (70, 80, "sssp.fetch"),
        (80, 100, "sssp.tick")]
    into = {}
    gaps = [(-5, 5), (15, 25), (58, 75), (90, 110)]
    trace_scopes._attribute(gaps, trace_scopes._innermost(anns), into, 1.0)
    assert into == {"none": 5 + 10, "sssp.tick": 5 + 10 + 10,
                    "sssp.launch": 5, "sssp.wait": 5, "sssp.fetch": 2 + 5}
    assert sum(into.values()) == sum(e - s for s, e in gaps)


def test_intersect_and_scope_of():
    assert trace_scopes._intersect([(0, 10), (20, 30)],
                                   [(5, 25), (28, 40)]) == [
        (5, 10), (20, 25), (28, 30)]
    assert trace_scopes.scope_of(
        "jit(sssp_frontier)/while/body/frontier.relax/while/body/"
        "frontier.compact/gather:") == "frontier.relax"
    assert trace_scopes.scope_of(
        "jit(sssp_multisource_csr)/while/cond/multisource_csr.test/ne:"
    ) == "multisource_csr.test"
    assert trace_scopes.scope_of("jit(f)/add:") == "other"


# -- the fetch readers -----------------------------------------------------------

def _span(name, t0, t1, **args):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1, args=args,
                                 duration=t1 - t0)


def _p2p_tick(t, fetch_s=(0.001, 0.004)):
    return [_span("tick", t, t + 1.0),
            _span("p2p_solve", t + 0.1, t + 0.5),
            _span("fetch", t + 0.45, t + 0.45 + fetch_s[0], bytes=9),
            _span("fetch", t + 0.6, t + 0.6 + fetch_s[1], bytes=160_000)]


def test_fetch_readers_on_a_synthetic_window():
    spans = _p2p_tick(0.0) + _p2p_tick(2.0, (0.002, 0.006))
    ctx = {"spans": spans}
    assert harness.reader("fetch_ms.p2p", ROOT)(ctx) == pytest.approx(
        (0.005 + 0.008) / 2 * 1e3)
    assert harness.reader("fetch_bytes.p2p", ROOT)(ctx) == 160_009
    assert harness.reader("fetch_ms.batch", ROOT)(ctx) is None
    batch = [_span("tick", 0.0, 1.0), _span("batch_solve", 0.1, 0.9),
             _span("fetch", 0.8, 0.803, bytes=640_005)]
    assert harness.reader("fetch_ms.batch", ROOT)(
        {"spans": batch}) == pytest.approx(3.0)


def test_fetch_readers_read_nothing_from_a_program_without_fetch_spans():
    spans = [s for s in _p2p_tick(0.0) if s.name != "fetch"]
    for name in ("fetch_ms.p2p", "fetch_bytes.p2p", "fetch_ms.batch"):
        assert harness.reader(name, ROOT)({"spans": spans}) is None
        assert harness.reader(name, ROOT)({"spans": []}) is None


def test_fetch_readers_on_the_served_path(tiny_root):
    import jax

    from bench import graphs, traffic
    from repro.core.csr import CsrGraph
    from repro.obs.trace import Tracer, set_tracer
    from repro.serve import DistanceCache, GraphRegistry, MicroBatchScheduler

    cell = harness.load_cell("tableii-40k.p2p", tiny_root)
    csr = graphs.build(cell.config, 5, 0,
                       graphs.generator(cell.config["generator"], tiny_root))
    warm, jobs = traffic.make_jobs(cell.mix, [csr], 5)
    registry = GraphRegistry()
    sched = MicroBatchScheduler(registry, DistanceCache(capacity=8))
    registry.register("g", CsrGraph(csr.indptr, csr.indices, csr.weights,
                                    csr.n))
    harness.drive(jax, sched, ["g"], warm, seconds=0.0)
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        harness.drive(jax, sched, ["g"], jobs, seconds=0.0)    # one job
    finally:
        set_tracer(prev)
    ctx = {"spans": tr.spans}
    # the row (n f32) after the solve, and sweeps + edges + flag in it
    assert harness.reader("fetch_bytes.p2p", ROOT)(ctx) == csr.n * 4 + 9
    assert harness.reader("fetch_ms.p2p", ROOT)(ctx) > 0
