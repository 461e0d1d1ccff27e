"""End-to-end SSSP pipeline reproducing the paper's workflow:

    edge list -> adjacency matrix (+ padding) -> engine -> verified output,

for every engine, with timings in the paper's §III cost envelope and a
cross-engine agreement check.

    PYTHONPATH=src python examples/sssp_pipeline.py [--nodes N] [--edges M]
"""
import argparse
import time

import numpy as np

import jax

from repro.core import graph as G
from repro.core._axes import make_mesh
from repro.core.api import (CSR_ENGINES, DELTA_ENGINES, ENGINES,
                            SHARDED_CSR_ENGINES, shortest_paths)
from repro.core.serial import dijkstra_serial_np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=800)
    ap.add_argument("--edges", type=int, default=2400)
    ap.add_argument("--source", type=int, default=0)
    args = ap.parse_args()

    # 1. edge list (the paper's input format) -> both containers
    g = G.random_graph(args.nodes, args.edges, seed=0)
    cg = g.to_csr()
    dense_bytes = g.adj.nbytes
    print(f"built adjacency matrix: {g.n}x{g.n}, {g.num_edges} edges "
          f"({dense_bytes / 1e6:.2f} MB dense)")
    print(f"built CSR container:    {cg.nnz} arcs "
          f"({cg.nbytes / 1e6:.2f} MB, {dense_bytes / cg.nbytes:.1f}x "
          "smaller — the paper's §V Table II complaint, fixed)")

    # 2. oracle
    ref, _ = dijkstra_serial_np(g.adj, args.source)

    # 3. every engine (sharded ones on a host mesh over available devices)
    n_dev = jax.device_count()
    mesh = make_mesh((n_dev,), ("data",)) if n_dev > 1 else None
    for engine in ENGINES:
        if (engine in ("dijkstra_sharded", "bellman_sharded")
                + SHARDED_CSR_ENGINES and mesh is None):
            print(f"  {engine:18s}: skipped (single device; "
                  "run under XLA_FLAGS=--xla_force_host_platform_device_count=8)")
            continue
        src = (np.array([args.source])
               if engine in ("multisource", "multisource_csr")
               else args.source)
        # CSR-native engines get the sparse container directly — no dense
        # matrix on their path at all.  The Δ engines additionally thread
        # delta="auto": the bucket width is derived per graph from the
        # staged weight profile (core/delta_stepping.auto_delta).
        arg_g = (cg if engine in CSR_ENGINES + DELTA_ENGINES
                 + SHARDED_CSR_ENGINES or engine == "multisource_csr" else g)
        kw = {"delta": "auto"} if engine in DELTA_ENGINES else {}
        shortest_paths(arg_g, src, engine=engine, mesh=mesh, **kw)  # warm jit
        t0 = time.perf_counter()
        res = shortest_paths(arg_g, src, engine=engine, mesh=mesh, **kw)
        dt = time.perf_counter() - t0
        got = res.dist[0] if res.dist.ndim == 2 else res.dist
        ok = np.allclose(np.where(np.isfinite(ref), ref, 1e30),
                         np.where(np.isfinite(got), got, 1e30), rtol=1e-5)
        print(f"  {engine:18s}: {dt:.5f}s  verify={'OK' if ok else 'FAIL'}")
        assert ok, engine
    print("all engines agree with the oracle")


if __name__ == "__main__":
    main()
