"""Paper Table IV / Fig. 6: strong scaling of the MPI-analogue Dijkstra.

Each process count runs in its own subprocess with
``--xla_force_host_platform_device_count`` (the MPI -np analogue on this
single-host container).  The paper's observation — scaling efficiency
collapses because each of the n iterations carries a MINLOC allreduce —
reproduces qualitatively; we additionally run the beyond-paper
``bellman_sharded`` engine (one collective per *sweep*) at the same sizes,
which is the fix the paper's §V.2 calls for.
"""
from __future__ import annotations

import re

from benchmarks.common import run_module, write_csv

PROCS = (1, 2, 4, 8, 16)


def _time_of(out: str) -> float:
    return float(re.search(r"time=([\d.e+-]+)s", out).group(1))


def run(quick: bool = False, n: int = 2048):
    n = 1024 if quick else n
    m = 3 * n
    rows = []
    base = {}
    for engine in ("dijkstra_sharded", "bellman_sharded"):
        for procs in PROCS if not quick else PROCS[:4]:
            out = run_module(
                "repro.launch.sssp_run",
                ["--engine", engine, "--procs", str(procs),
                 "--nodes", str(n), "--edges", str(m), "--repeats", "2"])
            t = _time_of(out)
            if procs == 1:
                base[engine] = t
            eff = base[engine] / (t * procs) * 100
            rows.append([engine, procs, f"{t:.6f}", f"{eff:.2f}"])
            print(f"{engine:18s} procs={procs:3d} time={t:.6f}s "
                  f"efficiency={eff:6.2f}%", flush=True)
    return write_csv("table4_scaling.csv",
                     ["engine", "procs", "time_s", "efficiency_pct"], rows)


if __name__ == "__main__":
    import sys
    run("--quick" in sys.argv)
