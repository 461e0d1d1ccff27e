"""Sharded serving route: dispatch seam + vertex-partitioned serving.

Covers the serve/dispatch.py policy logic (pure, any device count), the
``multisource_csr_sharded`` union-frontier engine's bitwise parity and
its strictly-smaller edge counter (P=1 in-process), shard-aware row
keys and registry partition staging, and — on a real multi-device mesh —
the scheduler's sharded batch/p2p paths end to end.  The in-process
multi-device tests skip on one device and run in CI's ``multidevice``
job (forced 4 host devices); the subprocess tests force their own
device counts and are slow-marked, like tests/test_sharded_csr.py.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from conftest import dijkstra_oracle
from repro.core import csr as C
from repro.core._axes import make_mesh
from repro.core.api import shortest_paths
from repro.serve import (DispatchPolicy, DistanceCache, GraphRegistry,
                         MicroBatchScheduler)
from repro.serve.dispatch import serving_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
NDEV = len(jax.devices())
multidevice = pytest.mark.skipif(
    NDEV < 2, reason="needs >1 device (CI multidevice job forces 4)")


# ---------------------------------------------------------------------------
# dispatch policy (pure logic, any device count)
# ---------------------------------------------------------------------------

def test_policy_would_shard_is_pure_size_check():
    pol = DispatchPolicy(shard_threshold=100)
    if pol.nprocs > 1:
        assert pol.would_shard(100) and pol.would_shard(101)
        assert not pol.would_shard(99)
    else:                       # one device: sharding is never worth it
        assert not pol.would_shard(10**9)
    assert not pol.would_shard(10**9, dynamic=True)
    assert not DispatchPolicy(shard_threshold=None).would_shard(10**9)


def test_policy_clamps_nprocs_to_visible_devices():
    # asking for more devices than are visible is an error, never a
    # silent clamp to fewer
    with pytest.raises(ValueError, match="visible"):
        DispatchPolicy(nprocs=NDEV + 1)
    assert DispatchPolicy(nprocs=NDEV).nprocs == NDEV
    assert DispatchPolicy(nprocs=1).nprocs == 1


def test_policy_single_device_choices():
    pol = DispatchPolicy(shard_threshold=None)
    cg = C.sparse_csr_graph(50, seed=0)
    for kind, engine in (("single", "frontier"),
                         ("batch", "multisource_csr"),
                         ("p2p", "frontier")):
        ch = pol.choose(cg, kind=kind)
        assert (ch.engine, ch.mesh, ch.nprocs) == (engine, None, 1)
        assert not ch.sharded
    with pytest.raises(ValueError, match="unknown kind"):
        pol.choose(cg, kind="bogus")


@multidevice
def test_policy_sharded_choices_and_cached_mesh():
    pol = DispatchPolicy(shard_threshold=100)
    big = C.sparse_csr_graph(200, seed=1)
    for kind, engine in (("single", "frontier_sharded"),
                         ("batch", "multisource_csr_sharded"),
                         ("p2p", "frontier_sharded")):
        ch = pol.choose(big, kind=kind)
        assert ch.engine == engine and ch.sharded
        assert ch.nprocs == pol.nprocs and ch.mesh is not None
    # below threshold stays single-device; the mesh is built once
    assert not pol.choose(C.sparse_csr_graph(50, seed=2)).sharded
    assert (pol.choose(big).mesh
            is serving_mesh(pol.nprocs, pol.axis))


@multidevice
def test_policy_never_shards_dynamic_graphs():
    from repro.dynamic import DynamicGraph

    pol = DispatchPolicy(shard_threshold=10)
    dg = DynamicGraph(C.sparse_csr_graph(200, seed=3))
    assert not pol.choose(dg, kind="batch").sharded
    # and a registered dynamic handle is equally pinned single-device
    reg = GraphRegistry()
    h = reg.register("d", dg)
    assert not pol.choose(h, kind="batch").sharded


# ---------------------------------------------------------------------------
# union-frontier multisource engine, P=1 in-process
# ---------------------------------------------------------------------------

def test_multisource_sharded_p1_bitwise_and_union_edges():
    """Per-source rows bitwise-equal to serial; the union-frontier edge
    counter is STRICTLY below the sum of per-source frontier counters
    whenever frontiers overlap (they always do from sweep 1 on a
    connected corpus: the counter is what gate_sharded measures)."""
    mesh = make_mesh((1,), ("data",))
    for n, m, seed in [(57, 170, 0), (500, 1500, 9)]:
        cg = C.random_csr_graph(n, m, seed=seed)
        srcs = [0, 3, 7, 11]
        res = shortest_paths(cg, srcs, engine="multisource_csr_sharded",
                             mesh=mesh)
        assert res.dist.shape == (4, n) and res.pred is None
        per_source = 0
        for i, s in enumerate(srcs):
            ref = shortest_paths(cg, s, engine="serial")
            assert np.array_equal(res.dist[i], ref.dist), (n, s)
            oracle = dijkstra_oracle(cg, s)
            fin = np.isfinite(oracle)
            assert np.allclose(res.dist[i][fin], oracle[fin], rtol=1e-5)
            per_source += shortest_paths(cg, s,
                                         engine="frontier").edges_relaxed
        assert 0 < res.edges_relaxed < per_source, (n, res.edges_relaxed,
                                                    per_source)


def test_multisource_sharded_p1_matches_multisource_csr():
    mesh = make_mesh((1,), ("data",))
    cg = C.sparse_csr_graph(300, seed=4)
    srcs = [5, 5, 12]                     # duplicate sources are fine
    sh = shortest_paths(cg, srcs, engine="multisource_csr_sharded",
                        mesh=mesh)
    sd = shortest_paths(cg, srcs, engine="multisource_csr")
    assert np.array_equal(sh.dist, sd.dist)
    assert np.array_equal(sh.sources, sd.sources)


def test_frontier_sharded_accepts_target_as_full_solve():
    """target= on frontier_sharded runs the full fixpoint (no early
    exit): identical bytes to the untargeted solve, pred included."""
    mesh = make_mesh((1,), ("data",))
    cg = C.sparse_csr_graph(200, seed=5)
    t = shortest_paths(cg, 7, engine="frontier_sharded", mesh=mesh,
                       target=20)
    full = shortest_paths(cg, 7, engine="frontier_sharded", mesh=mesh)
    assert np.array_equal(t.dist, full.dist)
    assert t.pred is not None and np.array_equal(t.pred, full.pred)


# ---------------------------------------------------------------------------
# registry staging + shard-aware keys
# ---------------------------------------------------------------------------

def test_row_key_carries_owner_shard():
    reg = GraphRegistry()
    h = reg.register("g", C.sparse_csr_graph(100, seed=6))   # loc_n = 25
    assert h.row_key(3) == ("g", 3)
    assert h.row_key(3, shards=4) == ("g", 0, 3)
    assert h.row_key(25, shards=4) == ("g", 1, 25)
    assert h.row_key(99, shards=4) == ("g", 3, 99)
    assert h.owner_shard(50, 4) == 2


def test_registry_partition_staging_memoized_and_accounted():
    reg = GraphRegistry()
    h = reg.register("g", C.sparse_csr_graph(64, seed=7))
    base = reg.bytes_in_use
    parts = h.partition(2)
    assert parts is h.partition(2)               # memoized per nprocs
    assert reg.bytes_in_use >= base + parts.nbytes
    mesh = serving_mesh(1)
    parts = h.partition(1)
    ops = h.partition_ops(mesh)
    assert ops is h.partition_ops(mesh)
    assert reg.bytes_in_use > base + parts.nbytes  # device arrays counted
    # a different arity restages (policy change, not the serving path)
    assert h.partition(4).nprocs == 4
    assert h.partition_ops(mesh) is not ops


def test_registry_partition_refuses_dynamic_graphs():
    from repro.dynamic import DynamicGraph

    reg = GraphRegistry()
    h = reg.register("d", DynamicGraph(C.sparse_csr_graph(32, seed=8)))
    with pytest.raises(ValueError, match="dynamic"):
        h.partition(2)


# ---------------------------------------------------------------------------
# scheduler sharded routing, in-process multi-device
# ---------------------------------------------------------------------------

@multidevice
def test_scheduler_sharded_batch_and_p2p_bitwise():
    pol = DispatchPolicy(shard_threshold=500)
    reg, cache = GraphRegistry(), DistanceCache(64)
    sched = MicroBatchScheduler(reg, cache, max_batch=8, dispatch=pol)
    cg = C.sparse_csr_graph(2000, seed=3)
    reg.register("big", cg)
    reg.register("small", C.sparse_csr_graph(100, seed=4))

    for s in (5, 9, 5, 700, 1999):
        sched.submit("big", s)
    sched.submit("small", 3)
    answers = sched.drain()
    assert sched.sharded_batches == 1 and sched.sharded_sources == 4
    assert sched.engine_batches == 2          # small went single-device
    for a in answers:
        if a.query.graph == "big":
            ref = shortest_paths(cg, a.query.source, engine="serial")
            assert np.array_equal(a.value, ref.dist), a.query.source
    # rows cached under (name, owner_shard, source) keys
    keys = cache.keys_for("big")
    assert keys and all(len(k) == 3 for k in keys)
    h = reg.get("big")
    assert all(k[1] == h.owner_shard(k[2], pol.nprocs) for k in keys)
    assert all(len(k) == 2 for k in cache.keys_for("small"))

    # sharded p2p: full fixpoint, bitwise, and (unlike the single-device
    # target= path) the complete row lands in the cache
    sched.submit("big", 42, 77)
    a = sched.drain()[0]
    ref = shortest_paths(cg, 42, engine="serial")
    assert np.float32(a.value) == ref.dist[77]
    assert sched.sharded_p2p == 1 and sched.sharded_edges > 0
    row = cache.peek(h.row_key(42, shards=pol.nprocs))
    assert row is not None and np.array_equal(row, ref.dist)
    sched.submit("big", 42, 99)               # repeat hits the cache
    assert sched.drain()[0].via == "cache"


@multidevice
def test_sharded_evicted_graph_race_fails_typed_while_live_serves():
    """The submit -> evict -> tick race on the SHARDED route: the evicted
    graph's queries answer ``graph_gone`` while another shard-routed
    graph drained in the same tick still serves bitwise-exact."""
    pol = DispatchPolicy(shard_threshold=500)
    reg, cache = GraphRegistry(), DistanceCache(64)
    sched = MicroBatchScheduler(reg, cache, max_batch=8, dispatch=pol)
    ga = C.sparse_csr_graph(1200, seed=21)
    gb = C.sparse_csr_graph(1200, seed=22)
    reg.register("ga", ga)
    reg.register("gb", gb)
    sched.submit("ga", 11)
    sched.submit("ga", 40, 900)
    sched.submit("gb", 17)
    reg.evict("ga")
    by_qid = {a.query.source: a for a in sched.tick()}
    for s in (11, 40):
        assert by_qid[s].status == "graph_gone" and not by_qid[s].ok
    live = by_qid[17]
    assert live.status == "ok" and live.exact
    assert np.array_equal(live.value,
                          shortest_paths(gb, 17, engine="serial").dist)
    assert sched.sharded_batches == 1             # gb really went sharded
    assert not cache.keys_for("ga")               # eviction purged rows


@multidevice
def test_scheduler_sharded_occupancy_and_bucket_padding():
    pol = DispatchPolicy(shard_threshold=100)
    reg, cache = GraphRegistry(), DistanceCache(64)
    sched = MicroBatchScheduler(reg, cache, max_batch=8, dispatch=pol)
    reg.register("g", C.sparse_csr_graph(400, seed=9))
    for s in (1, 2, 3):                       # 3 distinct -> bucket 4
        sched.submit("g", s)
    sched.tick()
    assert sched.sharded_batches == 1
    assert sched.mean_occupancy == pytest.approx(3 / 4)


# ---------------------------------------------------------------------------
# multi-device end-to-end (subprocesses force their own device counts)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sssp_serve_driver_sharded_replay_verifies():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)     # the driver forces its own count
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.sssp_serve", "--smoke",
         "--devices", "4", "--shard-threshold", "128"],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sharded route: 4 devices" in r.stdout
    assert r.stdout.count("verified bitwise vs serial") == 3
    # at least one scenario actually took the sharded engines
    assert " batches + " in r.stdout


@pytest.mark.slow
def test_auto_engine_routes_sharded_multidevice():
    code = """
import numpy as np
from repro.core import csr as C
from repro.core.api import shortest_paths
from repro.serve import DispatchPolicy, set_default_policy

set_default_policy(DispatchPolicy(shard_threshold=500))
cg = C.sparse_csr_graph(2000, seed=11)
res = shortest_paths(cg, 3, engine="auto")
assert res.engine == "frontier_sharded", res.engine
ref = shortest_paths(cg, 3, engine="serial")
assert np.array_equal(res.dist, ref.dist)
resb = shortest_paths(cg, [3, 7], engine="auto")
assert resb.engine == "multisource_csr_sharded", resb.engine
assert np.array_equal(resb.dist[0], ref.dist)
small = C.sparse_csr_graph(100, seed=12)
assert shortest_paths(small, 0, engine="auto").engine == "frontier"
print("AUTO_SHARDED_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, env=env, timeout=900)
    assert "AUTO_SHARDED_OK" in r.stdout, r.stdout + r.stderr
