"""Graph500's Kronecker generator: the graph of the Graph500 benchmark's
SSSP kernel (Graph500 specification 3.0, graph500.org, section "Graph
Generation" and kernel 3, with its reference code
``kronecker_generator.m``).

Configuration keys: ``scale`` (2**scale vertices), ``edgefactor``
(edgefactor * 2**scale edges drawn) and ``initiator`` ([A, B, C]; D =
1 - A - B - C; the specification's are 0.57, 0.19, 0.19).  Each edge
takes its row and column bit by bit, one level of the initiator a bit, as
the reference code does: the row bit is set where a uniform draw exceeds
A + B, the column bit where a second exceeds C / (C + D) if the row bit
is set and A / (A + B) if not.  The vertices are then relabelled by a
uniform random permutation, and each edge gets a weight uniform in
[0, 1).  The result goes through ``csr_from_edge_list``.

Assumed, where the specification leaves it open:
- the draws from a run's stream come in this order: each level's row
  bits then its column bits, level 0 first (the lowest bit), then the
  vertex permutation, then the weights;
- weights are float32 uniform in [0, 1), drawn in float32 so that none
  rounds up to 1 (the specification fixes no precision; the program
  serves float32);
- edges are undirected and stored as both arcs; self-loops are dropped
  and of parallel edges the least weight is kept (the specification lets
  kernel 1 do either, and a shortest path only ever takes the least);
- the reference code's last step, a shuffle of the edge list, is left
  out: the CSR sorts the arcs, and the weights are drawn independently
  of the edges' order, so the shuffle changes no graph's distribution.
"""
from __future__ import annotations

import numpy as np

from bench.graphs import Csr, csr_from_edge_list


def pairs(config: dict, rng: np.random.Generator) -> np.ndarray:
    """``(edgefactor * 2**scale, 2)`` int64 (row, column) of every edge,
    before the relabelling."""
    scale = config["scale"]
    m = config["edgefactor"] << scale
    a, b, c = config["initiator"]
    ab, c_norm, a_norm = a + b, c / (1.0 - a - b), a / (a + b)
    row = np.zeros(m, np.int64)
    col = np.zeros(m, np.int64)
    for level in range(scale):
        row_bit = rng.random(m) > ab
        col_bit = rng.random(m) > np.where(row_bit, c_norm, a_norm)
        row += row_bit * np.int64(1 << level)
        col += col_bit * np.int64(1 << level)
    return np.stack([row, col], axis=1)


def build(config: dict, rng: np.random.Generator) -> Csr:
    n = 1 << config["scale"]
    e = pairs(config, rng)
    e = rng.permutation(n)[e]
    w = rng.random(e.shape[0], dtype=np.float32)
    return csr_from_edge_list(n, e, w)


def tiny(config: dict) -> dict:
    """1,024 vertices and 16,384 edges drawn at edge factor 16."""
    return {"scale": 10}
