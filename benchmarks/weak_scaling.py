"""Weak scaling — the experiment the paper could NOT run (footnote 7: "the
current implementation cannot perform efficient weak scaling because ...
the graph file is difficult to generate").

Our generators are procedural, so weak scaling is one loop: hold vertices-
per-process constant (n = base_n × procs) and measure every sharded
engine.  The Dijkstra engine's time grows ~linearly with procs at fixed
n/proc (n total iterations, each a collective round) — the paper's
diagnosis again; the fixpoint engine stays near-flat until the sweep work
dominates.

The CSR engines (PR 3) run the same experiment at *sparse* scale: the
dense engines ship the O(n²) matrix (so n = 4096 at P=8 already means a
64 MB operand), while ``bellman_csr_sharded`` / ``frontier_sharded`` hold
O(m/P) per device and their weak-scaling point is the paper's footnote-7
experiment finally run with edges — frontier_sharded additionally keeps
the per-sweep exchange at O(|frontier|), the MPI-message analogue.
"""
from __future__ import annotations

import re

from benchmarks.common import run_module, write_csv

PROCS = (1, 2, 4, 8)
ENGINES = ("dijkstra_sharded", "bellman_sharded",
           "bellman_csr_sharded", "frontier_sharded")


def run(quick: bool = False, base_n: int = 512):
    base_n = 256 if quick else base_n
    rows = []
    for engine in ENGINES:
        # CSR engines never build the dense matrix: scale their leg 8x
        # further per process (still m = 3n, the Table II shape).
        eng_base = base_n if engine in ("dijkstra_sharded",
                                        "bellman_sharded") else 8 * base_n
        t1 = None
        for procs in PROCS:
            n = eng_base * procs
            out = run_module(
                "repro.launch.sssp_run",
                ["--engine", engine, "--procs", str(procs),
                 "--nodes", str(n), "--edges", str(3 * n),
                 "--repeats", "2"])
            t = float(re.search(r"time=([\d.e+-]+)s", out).group(1))
            t1 = t1 or t
            eff = t1 / t * 100            # weak-scaling efficiency
            rows.append([engine, procs, n, f"{t:.6f}", f"{eff:.2f}"])
            print(f"{engine:18s} procs={procs:2d} n={n:6d} "
                  f"time={t:.5f}s weak-eff={eff:6.1f}%", flush=True)
    return write_csv("weak_scaling.csv",
                     ["engine", "procs", "nodes", "time_s",
                      "weak_efficiency_pct"], rows)


if __name__ == "__main__":
    import sys
    run("--quick" in sys.argv)
