"""Paper Table III: dense vs sparse graphs at equal node counts.

The paper's claim: with an adjacency matrix, processing time depends on n,
not edge count.  We time the three implementations (serial = Alg.1;
bellman = the CUDA analogue's algorithm; dijkstra_sharded = the MPI
analogue, on an 8-device mesh in the same process) on the paper's graph
corpus.  The mesh is 8 emulated host devices on CPU; on an accelerator
host with fewer than 8 devices the run stops instead of sharing them.

CPU caveat recorded in EXPERIMENTS.md: absolute times are CPU times of the
TPU-targeted program (the kernel path runs in interpret mode); the
*density invariance* claim is what this table reproduces.
"""
from __future__ import annotations

from benchmarks.common import time_engine, write_csv
from repro.core import graph as G
from repro.core._axes import make_mesh
from repro.core.api import shortest_paths
from repro.launch.runtime import enable_compile_cache, use_devices

PROCS = 8

PAIRS = [
    (10, 30), (10, 45),
    (100, 300), (100, 4950),
    (1000, 3000), (1000, 499500),
    (2000, 6000), (2000, 1899500),
]


def run(quick: bool = False):
    pairs = PAIRS[:6] if quick else PAIRS
    mesh = make_mesh((PROCS,), ("data",), devices=use_devices(PROCS))
    rows = []
    for n, m in pairs:
        g = G.random_graph(n, m, seed=n + m)
        t_serial = time_engine(
            lambda: shortest_paths(g, 0, engine="serial"))
        t_bell = time_engine(
            lambda: shortest_paths(g, 0, engine="bellman"))
        t_mpi = time_engine(
            lambda: shortest_paths(g, 0, engine="dijkstra_sharded",
                                   mesh=mesh), repeats=2)
        rows.append([n, m, f"{t_serial:.6f}", f"{t_mpi:.6f}",
                     f"{t_bell:.6f}"])
        print(f"n={n:6d} m={m:8d} serial={t_serial:.6f}s "
              f"dijkstra_sharded(8)={t_mpi:.6f}s bellman={t_bell:.6f}s",
              flush=True)
    path = write_csv("table3_density.csv",
                     ["nodes", "edges", "serial_s", "mpi8_s", "bellman_s"],
                     rows)
    # density-invariance check (the paper's Table III conclusion)
    by_n = {}
    for n, m, ts, tm, tb in rows:
        by_n.setdefault(n, []).append(float(tb))
    for n, ts in by_n.items():
        if len(ts) == 2 and min(ts) > 0:
            ratio = max(ts) / min(ts)
            print(f"  density ratio n={n}: sparse/dense bellman "
                  f"time ratio {ratio:.2f} (paper: ~1)")
    return path


if __name__ == "__main__":
    import sys
    enable_compile_cache()
    run("--quick" in sys.argv)
