"""Plain reference: the binary-heap Dijkstra, in the arithmetic of a stated
precision.  It shares no code with the program and takes nothing from it:
it reads only the benchmark's own CSR arrays (``bench.graphs``).

Every relaxation rounds ``d + w`` once to the working precision, as the
device does for f32.  ``d + w`` of two f32 values computed in f64 and then
rounded to f32 is the correctly rounded f32 sum, and rounding is monotone,
so the settled labels are the least f32 left-fold sum over all paths: the
fixpoint every exact engine computes, bit for bit.  ``precision="bf16"``
rounds weights and sums to bfloat16 instead: the control, which a program
computing in that precision would match and the f32 reference must not.
"""
from __future__ import annotations

import heapq
import math
from array import array

import numpy as np

PRECISIONS = ("f32", "bf16")


def _bf16(x: float) -> float:
    """Round a non-negative finite float to the nearest bfloat16 (8
    significant bits, ties to even)."""
    if x == 0.0 or math.isinf(x):
        return x
    m, e = math.frexp(x)
    return math.ldexp(round(m * 256.0) / 256.0, e)


def _rounder(precision: str):
    if precision == "f32":
        r32 = array("f", [0.0])

        def f32(x: float) -> float:
            r32[0] = x
            return r32[0]
        return f32
    if precision == "bf16":
        return _bf16
    raise ValueError(f"precision must be one of {PRECISIONS}")


class Dijkstra:
    """Heap Dijkstra over one graph's outgoing arcs."""

    def __init__(self, csr, precision: str = "f32"):
        ptr, dst, w = csr.out_csr()
        rnd = _rounder(precision)
        self.n = csr.n
        self.round = rnd
        self.ptr = array("q", ptr.tobytes())
        self.dst = array("q", dst.astype(np.int64).tobytes())
        w64 = np.asarray(w, np.float64)
        if precision != "f32":
            w64 = np.array([rnd(float(x)) for x in w64])
        self.w = array("d", w64.tobytes())

    def solve(self, source: int, target: int | None = None):
        """Labels from ``source`` (``inf`` where unreached).  With
        ``target`` the search stops once the target is settled and returns
        its label alone."""
        ptr, dst, w, rnd = self.ptr, self.dst, self.w, self.round
        inf = math.inf
        dist = array("d", [inf]) * self.n
        done = bytearray(self.n)
        dist[source] = 0.0
        heap = [(0.0, source)]
        pop, push = heapq.heappop, heapq.heappush
        while heap:
            d, u = pop(heap)
            if done[u]:
                continue
            if u == target:
                return d
            done[u] = 1
            for e in range(ptr[u], ptr[u + 1]):
                v = dst[e]
                x = d + w[e]
                if x < dist[v]:
                    x = rnd(x)
                    if x < dist[v]:
                        dist[v] = x
                        push(heap, (x, v))
        if target is not None:
            return dist[target]
        return np.frombuffer(dist, np.float64).astype(np.float32)


# -- worker-process entry points (spawned; they never import JAX) --------

_WORKER: dict = {}


def init_worker(config: dict, seed: int, count: int, precision: str,
                root: str) -> None:
    """Rebuild a run's ``count`` graphs from its configuration and seed in
    a worker, with the generator of the checkout at ``root``."""
    from bench.graphs import build, generator

    gen = generator(config["generator"], root)
    _WORKER["dij"] = [Dijkstra(build(config, seed, g, gen), precision)
                      for g in range(count)]


def worker_solve(pair: tuple):
    graph, source = pair
    return _WORKER["dij"][graph].solve(source)
