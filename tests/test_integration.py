"""Subprocess integration tests: the multi-device SSSP engines and the
``sssp_run`` driver across process counts.

These spawn fresh Python processes so each can force its own XLA host
device count (the in-process suite stays on the single real device)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def _run(code=None, module=None, args=(), devices=1, timeout=600):
    e = dict(os.environ)
    e["PYTHONPATH"] = SRC + os.pathsep + e.get("PYTHONPATH", "")
    if devices > 1:
        e["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    cmd = [sys.executable]
    if code is not None:
        cmd += ["-c", code]
    else:
        cmd += ["-m", module, *args]
    return subprocess.run(cmd, capture_output=True, text=True, env=e,
                          timeout=timeout)


@pytest.mark.slow
def test_sharded_engines_multidevice_match_oracle():
    code = """
import jax, numpy as np, jax.numpy as jnp
from repro.core import graph as G
from repro.core.api import shortest_paths
from repro.core.serial import dijkstra_serial_np
from repro.core._axes import make_mesh
mesh = make_mesh((8,), ("data",))
g = G.random_graph(103, 400, seed=5)
ref, _ = dijkstra_serial_np(g.adj, 4)
for engine in ("dijkstra_sharded", "bellman_sharded"):
    res = shortest_paths(g, 4, engine=engine, mesh=mesh)
    ok = np.allclose(np.where(np.isfinite(ref), ref, 1e30),
                     np.where(np.isfinite(res.dist), res.dist, 1e30), rtol=1e-5)
    assert ok, engine
res = shortest_paths(g, np.array([4, 9]), engine="multisource", mesh=mesh)
ok = np.allclose(np.where(np.isfinite(ref), ref, 1e30),
                 np.where(np.isfinite(res.dist[0]), res.dist[0], 1e30), rtol=1e-5)
assert ok
print("MULTIDEVICE_OK")
"""
    r = _run(code=code, devices=8)
    assert "MULTIDEVICE_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_minloc_variants_agree_multidevice():
    code = """
import jax, numpy as np, jax.numpy as jnp
from repro.core import graph as G
from repro.core.sharded import dijkstra_sharded
from repro.core.serial import dijkstra_serial_np
from repro.core._axes import make_mesh
mesh = make_mesh((8,), ("data",))
g = G.random_graph(96, 380, seed=8).padded(8)
ref, _ = dijkstra_serial_np(g.adj, 0)
for impl in ("allgather", "pmin", "packed"):
    d, p = dijkstra_sharded(jnp.asarray(g.adj), 0, mesh, n_true=96, minloc=impl)
    d = np.asarray(d)[:96]
    assert np.allclose(np.where(np.isfinite(ref[:96]), ref[:96], 1e30),
                       np.where(np.isfinite(d), d, 1e30), rtol=1e-5), impl
print("MINLOC_OK")
"""
    r = _run(code=code, devices=8)
    assert "MINLOC_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_sssp_run_driver_scaling_procs():
    r = _run(module="repro.launch.sssp_run",
             args=["--engine", "dijkstra_sharded", "--procs", "4",
                   "--nodes", "200", "--edges", "600", "--verify",
                   "--repeats", "1"])
    assert r.returncode == 0, r.stderr
    assert "verify: OK" in r.stdout
