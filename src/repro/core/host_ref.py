"""Host references for checking device answers at deployment scale.

Both functions read only the incoming-CSR arrays of a ``CsrGraph``
(``indptr``, ``indices``, ``weights``) and share no code with any engine,
so an agreement is an independent derivation of the same answer.

* :func:`heap_dijkstra_f32` is the classic binary-heap Dijkstra in f32
  arithmetic.  Every relaxation rounds ``d + w`` to f32 exactly as the
  device does; f32 addition is monotone and never decreases a label for
  ``w >= 0``, so the settled labels are the minimum over paths of their
  left-fold f32 sums — the fixpoint every engine computes.  Device rows
  must therefore match it *bitwise*.
* :func:`check_f32_row` checks a row too costly to re-derive one by one
  in Python.  It proves the row is that same f32 fixpoint with O(m)
  vectorized tests, and cross-checks it against scipy's f64 Dijkstra
  (:func:`scipy_rows`) within the rounding bound of its path lengths:
  an f32 left-fold over k non-negative terms is within ``k·2^-24``
  (relative, to first order) of the exact sum, so no fixed tolerance
  fits long road paths.
"""
from __future__ import annotations

import heapq
from array import array

import numpy as np


def _out_adjacency(indptr, indices, weights, n):
    """Outgoing-arc lists of an incoming CSR: (ptr, dst, w) as compact
    arrays whose items read back as Python ints and floats."""
    deg = np.diff(np.asarray(indptr, np.int64))
    dst = np.repeat(np.arange(n, dtype=np.int64), deg)
    src = np.asarray(indices, np.int64)
    order = np.argsort(src, kind="stable")
    ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=ptr[1:])
    return (array("q", ptr.tobytes()),
            array("q", dst[order].tobytes()),
            array("d", np.asarray(weights, np.float64)[order].tobytes()))


def heap_dijkstra_f32(indptr, indices, weights, n: int,
                      source: int) -> np.ndarray:
    """(n,) float32 distances from ``source`` (``inf`` where unreached),
    f32 arithmetic throughout.  ``d + w`` of two f32 values computed in
    f64 and rounded once to f32 is the correctly rounded f32 sum (53 >=
    2·24 + 2 bits), and a candidate whose unrounded sum already reaches
    the current label cannot beat it after rounding, so only improving
    candidates are rounded."""
    ptr, dst, w = _out_adjacency(indptr, indices, weights, n)
    inf = float("inf")
    dist = array("d", [inf]) * n
    done = bytearray(n)
    r32 = array("f", [0.0])
    dist[source] = 0.0
    heap = [(0.0, source)]
    pop, push = heapq.heappop, heapq.heappush
    while heap:
        d, u = pop(heap)
        if done[u]:
            continue
        done[u] = 1
        for e in range(ptr[u], ptr[u + 1]):
            v = dst[e]
            x = d + w[e]
            if x < dist[v]:
                r32[0] = x
                x = r32[0]
                if x < dist[v]:
                    dist[v] = x
                    push(heap, (x, v))
    return np.frombuffer(dist, np.float64).astype(np.float32)


def scipy_rows(indptr, indices, weights, n: int, sources):
    """``(dist (S, n) float64, pred (S, n) int32)`` from each of
    ``sources`` by scipy's Dijkstra over the same arcs (``inf`` / -9999
    where unreached)."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import dijkstra

    # row v of the incoming CSR holds v's in-arcs: it is the transpose
    incoming = csr_matrix(
        (np.asarray(weights, np.float64), np.asarray(indices),
         np.asarray(indptr)), shape=(n, n))
    return dijkstra(incoming.T.tocsr(), directed=True,
                    indices=np.asarray(sources, np.int64),
                    return_predecessors=True)


def _tree_depth(pred: np.ndarray) -> int:
    """Largest hop count in a predecessor forest (roots: pred < 0), by
    pointer doubling: ``depth[v]`` counts the hops from v to ``up[v]``."""
    n = pred.shape[0]
    up = np.where(pred < 0, np.arange(n), pred).astype(np.int64)
    depth = (pred >= 0).astype(np.int64)
    while True:
        nxt = up[up]
        if np.array_equal(nxt, up):
            return int(depth.max(initial=0))
        depth = depth + depth[up]
        up = nxt


def _require(ok, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def check_f32_row(indptr, indices, weights, n: int, source: int,
                  dist, ref64, pred64) -> None:
    """Raise ``AssertionError`` unless ``dist`` is exactly the f32
    shortest-path row from ``source`` and agrees with the f64 reference
    row ``ref64`` (its predecessors ``pred64``) within f32 rounding.

    Exactness, with every weight positive: (a) ``dist[source] == 0``;
    (b) no arc improves any label in f32; (c) every other finite label is
    attained by a tight in-arc ``dist[u] + w == dist[v]`` that strictly
    raises ``dist[u]``.  (b) gives ``dist <= fixpoint`` along every path;
    (c) makes each label the f32 left-fold of a real path back to the
    source, so ``dist >= fixpoint`` — equal, bit for bit."""
    d = np.asarray(dist, np.float32)
    w = np.asarray(weights, np.float32)
    src = np.asarray(indices, np.int64)
    dst = np.repeat(np.arange(n, dtype=np.int64),
                    np.diff(np.asarray(indptr, np.int64)))
    _require(np.all(w > 0), "the certificate needs positive weights")
    _require(d.shape == (n,) and d[source] == 0, "source label is not 0")
    via = d[src] + w
    _require(not np.any(via < d[dst]), "some arc still improves a label")
    tight = np.isfinite(via) & (via == d[dst]) & (via > d[src])
    pred = np.full(n, -1, np.int64)
    pred[dst[tight]] = src[tight]
    pred[source] = -1
    need = np.isfinite(d)
    need[source] = False
    _require(np.all(pred[need] >= 0), "a label is attained by no arc")
    reach = np.isfinite(ref64)
    _require(np.array_equal(np.isfinite(d), reach), "reached sets differ")
    hops = max(_tree_depth(pred),
               _tree_depth(np.where(np.asarray(pred64) < 0, -1, pred64)))
    err = np.abs(d[reach].astype(np.float64) - ref64[reach])
    _require(np.all(err <= 2.0 * hops * 2.0 ** -24 * ref64[reach]),
             f"row differs from f64 by more than {hops} hops of rounding")
