"""The one traffic generator.  A traffic mix is a data file,
``bench/traffic/<mix>.json``, of parameters that this module reads.  One
closed-loop client sends a job, waits for all of its answers, and sends
the next.

``job``
    ``{"kind": "p2p", "rank_exponents": [...]}``: one ``dist(s, t)``
    query, ``t`` the vertex of Dijkstra rank ``2**k`` from ``s`` (``s``
    itself is rank 0; ties by vertex id).  Each source gives one job per
    exponent, in the order listed: the Dijkstra-rank method of the
    route-planning literature (Sanders and Schultes).
    ``{"kind": "rows", "sources_per_job": S}``: ``S`` full-row queries
    sent together.
``graphs``
    How many graphs of the configuration a run draws and serves; job
    ``i`` goes to graph ``i mod graphs``.  A graph's own structure sets
    the sweeps of every job on it, so more graphs a run average that out.
``sources``
    How many distinct sources a run draws on each graph, uniform over
    its vertices with an arc out, as Graph500 draws its search keys among
    the vertices of degree 1 or more (on a connected graph, every
    vertex).  Each graph's warm-up job takes others.  When the window
    answers every job it starts the list again.
``check_sources``
    How many of the sources the window answered have their answers
    compared with the reference (all of them where fewer).
``trace_jobs``
    How many jobs the ``--trace 1`` run records in the profiler.

Everything is drawn from the run's seed, as the graph is: every seed
sends jobs of the same sizes (the same rank strata, the same rows per
job) on another graph, from other sources.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

from bench import graphs

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_STREAM = 1      # a run's seed feeds the jobs from this stream


@dataclasses.dataclass(frozen=True)
class Job:
    queries: tuple          # ((source, target or None), ...)
    label: str = ""         # e.g. "rank2^12"
    graph: int = 0          # index of the run's graph it is sent to


def load(mix: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{mix}.json")) as f:
        return json.load(f)


def rng_for(seed: int, stream: int = TRAFFIC_STREAM,
            index: int = 0) -> np.random.Generator:
    return graphs.stream(stream, seed, index)


def _scipy_out(csr):
    from scipy.sparse import csr_matrix

    ptr, dst, w = csr.out_csr()
    return csr_matrix((np.asarray(w, np.float64), dst, ptr),
                      shape=(csr.n, csr.n))


def rank_targets(out, sources, ranks: list) -> np.ndarray:
    """``(len(sources), len(ranks))``: for each source the vertices of
    the given Dijkstra ranks (the source is rank 0; ties by vertex id)."""
    from scipy.sparse.csgraph import dijkstra

    n = out.shape[0]
    if max(ranks) >= n:
        raise ValueError(f"rank {max(ranks)} needs more than {n} vertices")
    found = np.empty((len(sources), len(ranks)), np.int64)
    chunk = max(1, 2 ** 22 // n)
    for i in range(0, len(sources), chunk):
        rows = dijkstra(out, directed=True, indices=sources[i:i + chunk])
        for j, d in enumerate(rows.reshape(-1, n)):
            order = np.argsort(d, kind="stable")     # by distance, then id
            hit = order[ranks]
            if not np.all(np.isfinite(d[hit])):
                raise ValueError(f"source {sources[i + j]} reaches fewer "
                                 f"than {max(ranks) + 1} vertices")
            found[i + j] = hit
    return found


def make_jobs(mix: dict, csrs: list, seed: int):
    """``(warmups, jobs)``: a warm-up job for each graph, which sends the
    window's shapes and stages the graph, and the window's jobs, taking
    the graphs in turn (module docstring)."""
    warm, lists = [], []
    for g, csr in enumerate(csrs):
        w, js = _graph_jobs(mix, csr, rng_for(seed, TRAFFIC_STREAM, g))
        warm.append(dataclasses.replace(w, graph=g))
        lists.append([dataclasses.replace(j, graph=g) for j in js])
    return warm, [j for turn in zip(*lists) for j in turn]


def _graph_jobs(mix: dict, csr, rng: np.random.Generator):
    job, count = mix["job"], mix["sources"]
    if job["kind"] == "p2p":
        per_job = 1
    elif job["kind"] == "rows":
        per_job = job["sources_per_job"]
    else:
        raise ValueError(f"unknown job kind {job['kind']!r}")
    if count % per_job:
        raise ValueError(f"{count} sources do not fill jobs of {per_job}")
    # the vertices with an arc out (``indices`` holds each arc's source)
    pool = np.flatnonzero(np.bincount(csr.indices, minlength=csr.n))
    if count + per_job > pool.size:
        raise ValueError(f"{count + per_job} distinct sources asked of "
                         f"{pool.size} vertices with an arc out")
    sources = pool[rng.choice(pool.size, size=count + per_job,
                              replace=False)]
    warm_src, sources = sources[:per_job], sources[per_job:]
    if job["kind"] == "p2p":
        out = _scipy_out(csr)
        ks = job["rank_exponents"]
        # warm-up: an adjacent target runs the same program in few sweeps
        s = int(warm_src[0])
        warm = Job(((s, int(out.indices[out.indptr[s]])),), label="warm")
        targets = rank_targets(out, sources, [2 ** k for k in ks])
        jobs = [Job(((int(s), int(t)),), f"rank2^{k}")
                for s, row in zip(sources, targets) for k, t in zip(ks, row)]
        return warm, jobs
    warm = Job(tuple((int(s), None) for s in warm_src), label="warm")
    jobs = [Job(tuple((int(s), None) for s in sources[i:i + per_job]),
                f"rows{per_job}") for i in range(0, count, per_job)]
    return warm, jobs
