"""chip_smoke.py off the chip, and the host references it checks against.

The script itself needs a TPU, so here only its refusal is tested: on the
CPU backend it must exit nonzero without printing a result line.  The
references in core/host_ref.py are tested at a size where the O(n^2)
``serial`` engine is affordable: the f32 heap Dijkstra must equal it bit
for bit, and the row certificate must accept an exact row and reject a
row one ulp off in either direction.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import csr as C
from repro.core.api import shortest_paths
from repro.core.host_ref import check_f32_row, heap_dijkstra_f32, scipy_rows

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "TPU" in proc.stderr


@pytest.fixture(scope="module")
def table2():
    """The paper's Table II corpus (m = 3n) at n = 2000."""
    return C.sparse_csr_graph(2000, seed=3)


def _arrays(cg):
    return cg.indptr, cg.indices, cg.weights, cg.n


@pytest.mark.parametrize("source", [0, 777, 1999])
def test_heap_dijkstra_f32_bitwise_equals_serial(table2, source):
    ref = shortest_paths(table2, source, engine="serial").dist
    got = heap_dijkstra_f32(*_arrays(table2), source)
    assert got.dtype == np.float32
    assert np.asarray(ref, np.float32).tobytes() == got.tobytes()


def test_row_certificate_accepts_exact_and_rejects_one_ulp(table2):
    source = 5
    row = heap_dijkstra_f32(*_arrays(table2), source)
    D, Pr = scipy_rows(*_arrays(table2), [source])
    check_f32_row(*_arrays(table2), source, row, D[0], Pr[0])
    v = int(np.argmax(np.where(np.isfinite(row), row, -1)))
    for toward in (np.float32(np.inf), np.float32(0)):
        bad = row.copy()
        bad[v] = np.nextafter(bad[v], toward)
        with pytest.raises(AssertionError):
            check_f32_row(*_arrays(table2), source, bad, D[0], Pr[0])
