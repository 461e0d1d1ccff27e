"""Paper Figs. 2/3: execution time vs graph size for the three engines,
plus the beyond-paper multisource batching amortization (per-source time
drops as the adjacency traffic is shared across sources)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import time_engine, write_csv
from repro.core import graph as G
from repro.core.api import shortest_paths

SIZES = (10, 100, 500, 1000, 2000, 4000)


def run(quick: bool = False):
    sizes = SIZES[:4] if quick else SIZES
    rows = []
    for n in sizes:
        g = G.sparse_graph(n, seed=n)
        t_serial = time_engine(lambda: shortest_paths(g, 0, engine="serial"))
        t_bell = time_engine(lambda: shortest_paths(g, 0, engine="bellman"))
        rows.append([n, 3 * n, f"{t_serial:.6f}", f"{t_bell:.6f}"])
        print(f"n={n:6d} serial={t_serial:.6f}s bellman={t_bell:.6f}s "
              f"speedup={t_serial / max(t_bell, 1e-12):.2f}x", flush=True)
    p1 = write_csv("fig23_size_sweep.csv",
                   ["nodes", "edges", "serial_s", "bellman_s"], rows)

    # multisource amortization (beyond-paper)
    n = sizes[-1]
    g = G.sparse_graph(n, seed=1)
    rows2 = []
    for s in (1, 4, 16, 64):
        srcs = np.arange(s) % n
        t = time_engine(lambda: shortest_paths(g, srcs, engine="multisource"))
        rows2.append([n, s, f"{t:.6f}", f"{t / s:.6f}"])
        print(f"multisource n={n} S={s:3d}: total={t:.5f}s "
              f"per-source={t / s:.5f}s", flush=True)
    write_csv("multisource_amortization.csv",
              ["nodes", "sources", "total_s", "per_source_s"], rows2)
    return p1


if __name__ == "__main__":
    import sys

    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()
    run("--quick" in sys.argv)
