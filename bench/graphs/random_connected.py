"""The paper's Table II corpus: ``m`` distinct undirected edges, a random
spanning path (so the graph is connected) and uniform random pairs, with
weights uniform(1, max_weight).  No self-loops and no parallel edges, so
every seed gives exactly ``2 m`` arcs: the same shapes, the same compiled
programs.

Configuration keys: ``n``, ``edges`` (m), ``max_weight``.
"""
from __future__ import annotations

import numpy as np

from bench.graphs import Csr, csr_from_edge_list


def build(config: dict, rng: np.random.Generator) -> Csr:
    n, m = config["n"], config["edges"]
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
    w = rng.uniform(1.0, config["max_weight"], size=m)
    return csr_from_edge_list(n, e, w)


def tiny(config: dict) -> dict:
    """A graph the CPU tests solve in about a second, still m = 3n."""
    return {"n": 2048, "edges": 6144, "arcs": 12288}
