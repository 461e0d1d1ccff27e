"""Paper Table II revisited: dense adjacency vs sparse CSR on the sparse
corpus (m = 3n).

The paper's §V diagnosis: the dense matrix costs O(n²) memory and the dense
sweep O(n²) work per relaxation regardless of density — its 40,000-vertex
Table II point needs a 1.6 GB matrix for 120k edges.  This benchmark puts
numbers on the fix: for each corpus size we report

  * memory: dense n²·4 bytes vs the CSR container's O(n + m) bytes,
  * time:   ``bellman`` (dense O(n²) sweep) vs ``bellman_csr`` (O(m)
            segment-min sweep), same fixpoint, same answers.

Above ``--dense-cap`` (default 10000) the dense engine is skipped — exactly
the regime the dense formulation cannot reach — while the CSR engine keeps
going through the full corpus.

    PYTHONPATH=src python -m benchmarks.table2_sparse_csr [--quick]
"""
from __future__ import annotations

import argparse

from benchmarks.common import time_engine, write_csv
from repro.core import csr as C
from repro.core import graph as G
from repro.core.api import shortest_paths


def run(quick: bool = False, dense_cap: int = 10000):
    pairs = [p for p in G.PAPER_SPARSE if p[0] <= (2000 if quick else 40000)]
    rows = []
    for n, m in pairs:
        cg = C.random_csr_graph(n, m, seed=n + m)
        dense_bytes = n * n * 4
        csr_bytes = cg.nbytes
        t_csr = time_engine(
            lambda: shortest_paths(cg, 0, engine="bellman_csr"))
        if n <= dense_cap:
            g = cg.to_dense()
            t_dense = time_engine(
                lambda: shortest_paths(g, 0, engine="bellman"))
            dense_s = f"{t_dense:.6f}"
        else:
            dense_s = "skipped"     # the paper's ceiling, made explicit
        rows.append([n, m, dense_bytes, csr_bytes,
                     f"{dense_bytes / csr_bytes:.1f}", dense_s,
                     f"{t_csr:.6f}"])
        print(f"n={n:6d} m={m:8d} dense={dense_bytes / 1e6:9.1f}MB "
              f"csr={csr_bytes / 1e6:7.2f}MB (x{dense_bytes / csr_bytes:6.1f}) "
              f"bellman={dense_s:>9s}s bellman_csr={t_csr:.6f}s", flush=True)
    path = write_csv(
        "table2_sparse_csr.csv",
        ["nodes", "edges", "dense_bytes", "csr_bytes", "mem_ratio",
         "bellman_s", "bellman_csr_s"],
        rows,
    )
    return path


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--dense-cap", type=int, default=10000)
    args = ap.parse_args()
    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()
    run(args.quick, dense_cap=args.dense_cap)
