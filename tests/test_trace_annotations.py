"""The program's spans on the profiler's clock, and the engines' named
stages.

- a `Tracer` span is also a ``jax.profiler.TraceAnnotation`` named
  ``sssp.<span>``: under ``jax.profiler.trace`` it lands on the host plane,
  nested as the spans are; the `NullTracer` records nothing there and
  allocates nothing;
- a scheduler solve opens ``launch`` / ``wait`` / ``fetch`` children, each
  ``fetch`` with the bytes it read, and the traced path reads the engine's
  scalars in one ``jax.device_get``;
- the served engines' ``jax.named_scope`` stages reach the compiled HLO's
  ``op_name`` metadata, which the device trace reports as ``tf_op``.
"""
import glob
import os
import re
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.array import ArrayImpl

from repro.core import csr as C
from repro.core.bellman_csr import csr_operands, sssp_multisource_csr
from repro.core.frontier import frontier_operands, sssp_frontier
from repro.obs import NULL_TRACER, Tracer, set_tracer
from repro.obs import trace as trace_mod
from repro.serve import DistanceCache, GraphRegistry, MicroBatchScheduler

FRONTIER_SCOPES = ("frontier.compact", "frontier.relax", "frontier.test")
MULTISOURCE_SCOPES = ("multisource_csr.relax", "multisource_csr.test")


def _host_events(logdir: str) -> list:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(trace_mod.ANNOTATION_PREFIX)]


def test_spans_land_on_the_profiler_host_plane_nested(tmp_path):
    tr = Tracer()
    with jax.profiler.trace(str(tmp_path / "on")):
        with tr.span("tick"):
            with tr.span("p2p_solve"):
                with tr.span("launch"):
                    jnp.arange(8).sum().block_until_ready()
            with tr.span("fetch"):
                pass
    events = {n: (s, e) for n, s, e in _host_events(str(tmp_path / "on"))}
    assert set(events) == {"sssp.tick", "sssp.p2p_solve", "sssp.launch",
                           "sssp.fetch"}

    def inside(child, parent):
        return (events[parent][0] <= events[child][0]
                and events[child][1] <= events[parent][1])

    assert inside("sssp.p2p_solve", "sssp.tick")
    assert inside("sssp.launch", "sssp.p2p_solve")
    assert inside("sssp.fetch", "sssp.tick")
    assert not inside("sssp.fetch", "sssp.p2p_solve")
    assert [s.name for s in tr.spans] == ["launch", "p2p_solve", "fetch",
                                          "tick"]

    with jax.profiler.trace(str(tmp_path / "off")):
        with NULL_TRACER.span("tick"):
            with NULL_TRACER.span("p2p_solve"):
                jnp.arange(8).sum().block_until_ready()
    assert _host_events(str(tmp_path / "off")) == []


def test_null_tracer_is_allocation_free(monkeypatch):
    def no_annotation(name):
        raise AssertionError(f"NullTracer opened an annotation for {name}")

    monkeypatch.setattr(trace_mod, "_annotate", no_annotation)
    tr = NULL_TRACER
    assert tr.span("tick") is tr.span("fetch", bytes=4)    # one shared ctx
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for _ in range(2000):
            with tr.span("tick"):
                with tr.span("fetch"):
                    pass
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    only = [tracemalloc.Filter(True, trace_mod.__file__)]
    grown = [d for d in after.filter_traces(only).compare_to(
        before.filter_traces(only), "lineno") if d.size_diff > 0]
    assert grown == []
    assert tr.spans == [] and tr.instants == []


# -------------------------------------------------------- scheduler spans


def _scheduler(cg):
    registry = GraphRegistry()
    sched = MicroBatchScheduler(registry, DistanceCache(capacity=64),
                                max_batch=4)
    registry.register("g", cg)
    return sched


def _traced_tick(sched, queries):
    for s, t in queries:
        sched.submit("g", s, t)
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        answers = sched.tick()
    finally:
        set_tracer(prev)
    return tr, answers


def _children(tr, parent_name):
    (parent,) = [s for s in tr.spans if s.name == parent_name]
    return [s for s in tr.spans if s.depth == parent.depth + 1
            and parent.t0 <= s.t0 and s.t1 <= parent.t1]


@pytest.fixture(scope="module")
def graph():
    return C.random_csr_graph(96, 288, seed=4)


def test_p2p_tick_opens_launch_wait_fetch(graph):
    sched = _scheduler(graph)
    _traced_tick(sched, [(1, 7)])                       # warm: compile
    tr, answers = _traced_tick(sched, [(2, 9)])
    assert [a.via for a in answers] == ["target"]
    solve = [s.name for s in _children(tr, "p2p_solve")]
    assert solve == ["stage", "launch", "wait", "fetch"]
    (scalars,) = [s for s in _children(tr, "p2p_solve") if s.name == "fetch"]
    assert scalars.args["bytes"] == 4 + 4 + 1           # sweeps, edges, conv
    # the row read stays where it was: after the solve, a child of tick
    tick_kids = [s.name for s in _children(tr, "tick")]
    assert tick_kids == ["p2p_solve", "fetch"]
    (row,) = [s for s in _children(tr, "tick") if s.name == "fetch"]
    assert row.args["bytes"] == graph.n * 4


def test_batch_tick_opens_launch_wait_fetch(graph):
    sched = _scheduler(graph)
    _traced_tick(sched, [(1, None), (2, None), (3, None)])
    tr, answers = _traced_tick(sched, [(4, None), (5, None), (6, None)])
    assert [a.via for a in answers] == ["batch"] * 3
    kids = _children(tr, "batch_solve")
    assert [s.name for s in kids] == ["stage", "launch", "wait", "fetch"]
    # bucket 4: four f32 rows, the int32 sweep count and the flag
    assert kids[-1].args["bytes"] == 4 * graph.n * 4 + 4 + 1
    assert [s.name for s in _children(tr, "tick")] == ["batch_solve"]


def test_traced_p2p_reads_the_scalars_in_one_transfer(graph, monkeypatch):
    sched = _scheduler(graph)
    _traced_tick(sched, [(1, 7)])
    calls, stray, inside = [], [], [False]
    real_get, real_value = jax.device_get, ArrayImpl._value

    def device_get(x):
        calls.append([np.shape(a) for a in jax.tree_util.tree_leaves(x)])
        inside[0] = True
        try:
            return real_get(x)
        finally:
            inside[0] = False

    def value(self):
        if not inside[0]:
            stray.append(self.shape)
        return real_value.fget(self)

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(ArrayImpl, "_value", property(value))
    _traced_tick(sched, [(3, 11)])
    assert calls == [[(), (), ()], [(graph.n,)]]
    assert stray == []                  # no other blocking host read


# ------------------------------------------------------------ named stages


def _op_names(lowered) -> str:
    return " ".join(re.findall(r'op_name="([^"]*)"',
                               lowered.compile().as_text()))


@pytest.mark.parametrize("target", [None, 5])
def test_frontier_stages_reach_the_compiled_op_names(graph, target):
    kw = {} if target is None else {"target": jnp.int32(target)}
    names = _op_names(sssp_frontier.lower(
        frontier_operands(graph), jnp.int32(0), n=graph.n, **kw))
    for scope in FRONTIER_SCOPES:
        assert f"/{scope}/" in names, scope


def test_multisource_stages_reach_the_compiled_op_names(graph):
    names = _op_names(sssp_multisource_csr.lower(
        csr_operands(graph), jnp.arange(4, dtype=jnp.int32), n=graph.n))
    for scope in MULTISOURCE_SCOPES:
        assert f"/{scope}/" in names, scope
