"""Vertex-partitioned sharded CSR engines (core/sharded_csr.py).

Covers: the CsrPartition view's invariants (arc-set roundtrip, ascending
local segment ids, inert sentinel padding, out-CSR window consistency),
the ~1/P per-device memory claim, P=1 in-process parity (bitwise vs
serial, pred vs bellman_csr, edges_relaxed vs the single-device frontier
engine), and — via subprocesses with forced host device counts, like the
other multi-device tests — bitwise parity with serial on the Table II
sparse corpus through n=10000 for P in {2, 4, 8}.
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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


# ---------------------------------------------------------------------------
# partition view
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nprocs", [1, 2, 3, 8])
def test_partition_roundtrips_arc_set(nprocs):
    cg = C.random_csr_graph(57, 170, seed=11)
    parts = cg.partitioned(nprocs)
    assert parts.n_pad == parts.loc_n * nprocs and parts.n_pad >= cg.n
    got = set()
    for p in range(nprocs):
        real = np.isfinite(parts.in_w[p])
        # ascending local dst (segment-min precondition), incl. padding
        assert (np.diff(parts.in_dst_loc[p]) >= 0).all()
        for s, dl, w in zip(parts.in_src[p][real],
                            parts.in_dst_loc[p][real],
                            parts.in_w[p][real]):
            got.add((int(s), int(dl) + p * parts.loc_n, float(w)))
        # out view holds the same arcs behind the per-source windows
        out = set()
        for u in range(parts.n_pad + 1):
            lo, hi = parts.out_indptr[p, u], parts.out_indptr[p, u + 1]
            for e in range(lo, hi):
                out.add((int(u), int(parts.out_dst_loc[p, e]) + p * parts.loc_n,
                         float(parts.out_w[p, e])))
        assert out == {a for a in got
                       if a[1] // parts.loc_n == p}
    want = {(int(u), int(v), float(w)) for u, v, w in
            zip(cg.indices, cg.dst_ids(), cg.weights)}
    assert got == want


def test_partition_sentinel_row_is_empty():
    cg = C.sparse_csr_graph(40, seed=2)
    parts = cg.partitioned(4)
    # the frontier engines index row n_pad for dead compaction slots
    assert (parts.out_indptr[:, parts.n_pad + 1]
            == parts.out_indptr[:, parts.n_pad]).all()


def test_partition_per_device_memory_is_1_over_p():
    """Per-device edge arrays ~1/P of the single-device staged equivalent
    (csr_operands' src/dst/w 12 B/arc + frontier_operands' out dst/w
    8 B/arc = 20 B/arc); the out_indptr index stays O(n) per device."""
    cg = C.sparse_csr_graph(10000, seed=7)
    single = 20 * cg.nnz
    for P in (2, 4, 8):
        parts = cg.partitioned(P)
        assert parts.per_device_edge_bytes <= 1.3 * single / P, (
            P, parts.per_device_edge_bytes, single)
        assert parts.per_device_index_bytes <= 4 * (parts.n_pad + 2)


def test_partition_rejects_bad_nprocs():
    with pytest.raises(ValueError):
        C.sparse_csr_graph(10, seed=0).partitioned(0)


# ---------------------------------------------------------------------------
# engines, P=1 in-process (the real multi-device runs are subprocesses)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["bellman_csr_sharded", "frontier_sharded"])
def test_sharded_csr_engines_p1_match_oracle_and_serial(engine):
    mesh = make_mesh((1,), ("data",))
    for n, m, directed, seed in [(57, 170, False, 0), (103, 300, True, 3),
                                 (500, 1500, False, 9)]:
        cg = C.random_csr_graph(n, m, seed=seed, directed=directed)
        res = shortest_paths(cg, 4, engine=engine, mesh=mesh)
        ref = shortest_paths(cg, 4, engine="serial")
        assert np.array_equal(res.dist, ref.dist), (engine, n, directed)
        oracle = dijkstra_oracle(cg, 4)
        fin = np.isfinite(oracle)
        assert np.allclose(res.dist[fin], oracle[fin], rtol=1e-5)
        assert (np.isfinite(res.dist) == fin).all()
        # same deterministic lowest-u pred tie-break as the CSR family
        bp = shortest_paths(cg, 4, engine="bellman_csr").pred
        assert np.array_equal(res.pred, bp)


def test_frontier_sharded_p1_edge_counter_matches_single_device():
    """Same work, partitioned: each arc has exactly one owner, so the psum
    of per-owner counters equals the single-device frontier counter."""
    mesh = make_mesh((1,), ("data",))
    cg = C.sparse_csr_graph(500, seed=5)
    sh = shortest_paths(cg, 0, engine="frontier_sharded", mesh=mesh)
    sd = shortest_paths(cg, 0, engine="frontier")
    assert sh.edges_relaxed == sd.edges_relaxed
    assert sh.sweeps == sd.sweeps


def test_sharded_csr_single_vertex_and_edgeless():
    mesh = make_mesh((1,), ("data",))
    cg = C.csr_from_edge_list(1, np.zeros((0, 2)), np.zeros((0,)))
    for engine in ("bellman_csr_sharded", "frontier_sharded"):
        res = shortest_paths(cg, 0, engine=engine, mesh=mesh)
        assert res.dist.shape == (1,) and res.dist[0] == 0.0
    cg = C.csr_from_edge_list(5, np.zeros((0, 2)), np.zeros((0,)))
    res = shortest_paths(cg, 2, engine="frontier_sharded", mesh=mesh)
    assert res.dist[2] == 0.0 and np.isinf(np.delete(res.dist, 2)).all()


def test_sharded_csr_engines_need_mesh():
    cg = C.sparse_csr_graph(10, seed=0)
    with pytest.raises(ValueError, match="needs a mesh"):
        shortest_paths(cg, 0, engine="bellman_csr_sharded")


# ---------------------------------------------------------------------------
# multi-device bitwise parity (Table II corpus through n=10000)
# ---------------------------------------------------------------------------

_MULTIDEV_CODE = """
import numpy as np
from repro.core import csr as C
from repro.core._axes import make_mesh
from repro.core.api import shortest_paths

P = {procs}
mesh = make_mesh((P,), ("data",))
for n in (103, 1000, 10000):
    cg = C.sparse_csr_graph(n, seed=n)          # Table II shape: m = 3n
    ref = shortest_paths(cg, 0, engine="serial")
    fr = shortest_paths(cg, 0, engine="frontier")
    for engine in ("bellman_csr_sharded", "frontier_sharded"):
        res = shortest_paths(cg, 0, engine=engine, mesh=mesh)
        assert res.dist.shape == ref.dist.shape
        assert np.array_equal(res.dist, ref.dist), (engine, n)
        assert np.array_equal(res.pred, fr.pred), (engine, n)
    assert res.edges_relaxed == fr.edges_relaxed, n   # frontier_sharded
print("SHARDED_CSR_OK")
"""


@pytest.mark.slow
@pytest.mark.parametrize("procs", [2, 4, 8])
def test_sharded_csr_bitwise_vs_serial_multidevice(procs):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={procs}"
    r = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_CODE.format(procs=procs)],
        capture_output=True, text=True, env=env, timeout=900)
    assert "SHARDED_CSR_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_sssp_run_driver_sharded_csr_procs():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.sssp_run",
         "--engine", "frontier_sharded", "--procs", "4",
         "--nodes", "2000", "--edges", "6000", "--verify", "--repeats", "1"],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    assert "verify: OK" in r.stdout
