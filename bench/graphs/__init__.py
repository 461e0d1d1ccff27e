"""Graph generators of the benchmark, driven by a configuration file and a
run's seed.  numpy only, and independent of the program, so that a change
to the program cannot change the inputs it is measured on.

A graph is returned as incoming-arc CSR arrays: row ``v`` of ``indptr`` /
``indices`` / ``weights`` holds the arcs ``u -> v`` sorted by ``u``, the
layout the program's ``CsrGraph`` takes.  Undirected edges are stored in
both directions; self-loops are dropped and parallel edges keep the least
weight.
"""
from __future__ import annotations

import dataclasses

import numpy as np

GRAPH_STREAM = 0        # a run's seed feeds the graph from this stream


@dataclasses.dataclass(frozen=True)
class Csr:
    indptr: np.ndarray      # (n+1,) int64
    indices: np.ndarray     # (arcs,) int32, source of each incoming arc
    weights: np.ndarray     # (arcs,) float32
    n: int

    @property
    def arcs(self) -> int:
        return int(self.indices.shape[0])

    def out_csr(self) -> tuple:
        """Outgoing-arc view ``(ptr, dst, w)``: row ``u`` lists ``u -> v``."""
        dst = np.repeat(np.arange(self.n, dtype=np.int64),
                        np.diff(self.indptr))
        src = self.indices.astype(np.int64)
        order = np.argsort(src, kind="stable")
        ptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=self.n), out=ptr[1:])
        return ptr, dst[order], self.weights[order]


def csr_from_edge_list(n: int, edges: np.ndarray, weights: np.ndarray,
                       directed: bool = False) -> Csr:
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    w = np.asarray(weights, np.float32).reshape(-1)
    if edges.size and (edges.min() < 0 or edges.max() >= n):
        raise IndexError(f"edge endpoints must be in [0, {n})")
    u, v = edges[:, 0], edges[:, 1]
    if not directed:
        u, v = np.concatenate([u, v]), np.concatenate([v, u])
        w = np.concatenate([w, w])
    keep = u != v
    u, v, w = u[keep], v[keep], w[keep]
    key = v * np.int64(n) + u
    uniq, inv = np.unique(key, return_inverse=True)
    wmin = np.full(uniq.shape[0], np.inf, np.float32)
    np.minimum.at(wmin, inv, w)
    dst = uniq // n
    src = (uniq % n).astype(np.int32)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(dst, minlength=n), out=indptr[1:])
    return Csr(indptr, src, wmin, n)


def random_connected(n: int, m: int, *, rng: np.random.Generator,
                     max_weight: float) -> Csr:
    """The paper's Table II corpus: ``m`` distinct undirected edges, a
    random spanning path (so the graph is connected) and uniform random
    pairs, with weights uniform(1, max_weight).  No self-loops and no
    parallel edges, so every seed gives exactly ``2 m`` arcs: the same
    shapes, the same compiled programs."""
    if not n - 1 <= m <= n * (n - 1) // 2:
        raise ValueError(f"{m} edges cannot connect {n} vertices simply")
    perm = rng.permutation(n)
    u, v = perm[:-1], perm[1:]
    keys = np.minimum(u, v) * np.int64(n) + np.maximum(u, v)
    while keys.size < m:
        need = m - keys.size
        a = rng.integers(0, n, size=2 * need + 16)
        b = rng.integers(0, n, size=2 * need + 16)
        new = (np.minimum(a, b) * np.int64(n) + np.maximum(a, b))[a != b]
        _, first = np.unique(new, return_index=True)
        new = new[np.sort(first)]               # first draw of each pair
        new = new[~np.isin(new, keys)][:need]
        keys = np.concatenate([keys, new])
    e = np.stack([keys // n, keys % n], axis=1)
    w = rng.uniform(1.0, max_weight, size=m)
    return csr_from_edge_list(n, e, w)


def stream(kind: int, seed: int, index: int = 0) -> np.random.Generator:
    """The generator of a run's ``seed`` for one kind of input and one of
    its graphs (graph 0 keeps the two-word key of a one-graph run)."""
    key = [kind, seed % 2 ** 64] + ([index] if index else [])
    return np.random.default_rng(key)


def build(config: dict, seed: int, index: int = 0) -> Csr:
    """Graph ``index`` of a run: the graph a configuration file describes,
    drawn from the run's ``seed``.  Every seed and index gives the
    configuration's sizes, and another graph of them."""
    rng = stream(GRAPH_STREAM, seed, index)
    if config["generator"] == "random_connected":
        return random_connected(config["n"], config["edges"], rng=rng,
                                max_weight=config["max_weight"])
    raise ValueError(f"unknown generator {config['generator']!r}")
