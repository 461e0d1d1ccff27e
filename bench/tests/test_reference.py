"""The plain reference: exact f32 labels, an early exit that changes
nothing, and a bf16 control that differs."""
import numpy as np
import pytest
from scipy.sparse.csgraph import dijkstra

from bench import graphs, reference, traffic
from bench.tests.conftest import build
from bench.tests.test_inputs import RANDOM


def test_integer_weights_give_scipy_exactly():
    """With integer weights every f32 sum is exact, so the f32 reference
    must equal scipy's f64 Dijkstra to the bit."""
    e = np.array([[0, 1], [1, 2], [0, 2], [2, 3], [3, 4], [1, 4]])
    g = graphs.csr_from_edge_list(5, e, np.array([3, 4, 9, 1, 7, 20]))
    want = dijkstra(traffic._scipy_out(g), directed=True, indices=0)
    got = reference.Dijkstra(g).solve(0)
    assert got.dtype == np.float32
    assert got.tobytes() == want.astype(np.float32).tobytes()


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 9])
def test_labels_are_the_f32_fixpoint(seed):
    """No arc improves a label in f32, and every label but the source's
    is attained by an arc: the certificate of the least f32 fixpoint."""
    g = build(RANDOM, seed)
    d = reference.Dijkstra(g).solve(7)
    dst = np.repeat(np.arange(g.n), np.diff(g.indptr))
    via = d[g.indices] + g.weights
    assert d[7] == 0 and np.all(np.isfinite(d))
    assert not np.any(via < d[dst])
    tight = np.zeros(g.n, bool)
    tight[dst[via == d[dst]]] = True
    assert tight[np.arange(g.n) != 7].all()


def test_early_exit_returns_the_full_solve_label():
    g = build(RANDOM, 2)
    dij = reference.Dijkstra(g)
    row = dij.solve(3)
    for t in (0, 3, 17, 499):
        assert np.float32(dij.solve(3, t)) == row[t]


def test_bf16_control_differs_from_f32():
    g = build(RANDOM, 3)
    f32 = reference.Dijkstra(g).solve(0)
    bf16 = reference.Dijkstra(g, "bf16").solve(0)
    assert np.count_nonzero(f32 != bf16) > g.n // 2


def test_bf16_rounding_keeps_eight_significant_bits():
    assert reference._bf16(1.0) == 1.0
    assert reference._bf16(257.0) == 256.0          # tie to even
    assert reference._bf16(259.0) == 260.0
    assert reference._bf16(1.0 + 2 ** -9) == 1.0
    assert reference._bf16(float("inf")) == float("inf")
