"""Graph generators of the benchmark, driven by a configuration file and a
run's seed.  numpy only, and independent of the program, so that a change
to the program cannot change the inputs it is measured on.

A graph is returned as incoming-arc CSR arrays: row ``v`` of ``indptr`` /
``indices`` / ``weights`` holds the arcs ``u -> v`` sorted by ``u``, the
layout the program's ``CsrGraph`` takes.  Undirected edges are stored in
both directions; self-loops are dropped and parallel edges keep the least
weight.

Each generator is a module of its own, ``bench/graphs/<generator>.py``,
found by the name in a configuration's ``generator`` key, so a new one is
a new file.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import re

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


def stream(kind: int, seed: int, index: int = 0) -> np.random.Generator:
    """The generator of a run's ``seed`` for one kind of input and one of
    its graphs (graph 0 keeps the two-word key of a one-graph run)."""
    key = [kind, seed % 2 ** 64] + ([index] if index else [])
    return np.random.default_rng(key)


def generator(name: str, root: str):
    """The generator module ``bench/graphs/<name>.py`` of the checkout at
    ``root``, found by the name a configuration's ``generator`` gives.
    It has ``build(config, rng) -> Csr`` and ``tiny(config) -> dict``,
    the keys that cut the configuration to a size the CPU tests run in
    about a second."""
    where = os.path.join(root, "bench", "graphs")
    path = os.path.join(where, f"{name}.py")
    if os.path.basename(name) != name or not os.path.isfile(path):
        present = sorted(f[:-3] for f in os.listdir(where)
                         if f.endswith(".py") and not f.startswith("_"))
        raise ValueError(f"unknown generator {name!r}; the generator files "
                         f"in {where} are {present}")
    spec = importlib.util.spec_from_file_location(
        "bench_graph_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(config: dict, seed: int, index: int, gen) -> Csr:
    """Graph ``index`` of a run: the graph a configuration file describes,
    drawn from the run's ``seed`` by ``gen``, the generator module it
    names.  Every seed and index gives another graph of the
    configuration."""
    return gen.build(config, stream(GRAPH_STREAM, seed, index))
