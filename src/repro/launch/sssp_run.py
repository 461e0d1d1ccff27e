"""Paper-reproduction driver: run any SSSP engine on any graph.

    PYTHONPATH=src python -m repro.launch.sssp_run \
        --engine bellman_kernel --nodes 2000 --edges 6000
    PYTHONPATH=src python -m repro.launch.sssp_run \
        --engine dijkstra_sharded --procs 8 --nodes 4000 --edges 12000
    PYTHONPATH=src python -m repro.launch.sssp_run \
        --engine delta_stepping --corpus road --nodes 10000 --delta auto

Timing follows the paper's §III cost envelope: graph construction (edge
list -> adjacency matrix) is excluded; device transfer + algorithm + result
gather are included.
"""
import argparse
import time

import jax
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default="serial",
                    choices=["serial", "dijkstra_sharded", "bellman",
                             "bellman_kernel", "bellman_sharded",
                             "multisource", "bellman_csr",
                             "bellman_csr_kernel", "frontier",
                             "frontier_kernel", "delta_stepping",
                             "delta_stepping_kernel", "multisource_csr",
                             "bellman_csr_sharded", "frontier_sharded"])
    ap.add_argument("--nodes", type=int, default=1000)
    ap.add_argument("--edges", type=int, default=3000)
    ap.add_argument("--delta", default=None,
                    help="Δ bucket width: a positive float or 'auto' "
                         "(per-graph width from the weight profile).  "
                         "Consumed by the frontier and delta_stepping "
                         "engines; the Δ engines default to auto.")
    ap.add_argument("--corpus", default="random",
                    choices=["random", "road", "hub"],
                    help="graph shape: 'road' (4-neighbour grid, --nodes "
                         "rounded down to a square) and 'hub' (heavy-"
                         "tailed hub fan-outs) are the Δ-stepping gate "
                         "corpora; CSR-native engines only")
    ap.add_argument("--procs", type=int, default=1)
    ap.add_argument("--source", type=int, default=0)
    ap.add_argument("--sources", type=int, default=8,
                    help="batch size for the multisource engines")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--directed", action="store_true",
                    help="the paper's -w flag")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--verify", action="store_true")
    args = ap.parse_args(argv)

    from repro.launch.runtime import enable_compile_cache, use_devices

    enable_compile_cache()
    use_devices(args.procs)

    from repro.core import csr as C
    from repro.core import graph as G
    from repro.core._axes import make_mesh
    from repro.core.api import (DELTA_ENGINES, SHARDED_CSR_ENGINES,
                                shortest_paths)
    from repro.core.serial import dijkstra_serial_np

    csr_native = args.engine in SHARDED_CSR_ENGINES + DELTA_ENGINES
    if args.corpus != "random":
        if not (csr_native or args.engine in
                ("bellman_csr", "bellman_csr_kernel", "frontier",
                 "frontier_kernel", "multisource_csr")):
            ap.error(f"--corpus {args.corpus} builds a CsrGraph; "
                     f"engine {args.engine!r} needs the dense corpus")
        make = (C.road_like_csr_graph if args.corpus == "road"
                else C.skewed_hub_csr_graph)
        g = make(args.nodes, seed=args.seed)
        csr_native = True
    elif csr_native:
        # --procs for the CSR engines: same flag, sparse partition — no
        # dense matrix is ever built, so n can go far beyond the dense cap.
        g = C.random_csr_graph(args.nodes, args.edges, seed=args.seed,
                               directed=args.directed)
    else:
        g = G.random_graph(args.nodes, args.edges, seed=args.seed,
                           directed=args.directed)
    delta = args.delta
    if delta is not None and delta != "auto":
        delta = float(delta)   # api re-validates (positive, finite)
    mesh = None
    if args.engine in ("dijkstra_sharded", "bellman_sharded",
                       "multisource") + SHARDED_CSR_ENGINES:
        mesh = make_mesh((args.procs,), ("data",),
                         devices=jax.devices()[:args.procs])

    source = (np.arange(args.sources) % args.nodes
              if args.engine in ("multisource", "multisource_csr")
              else args.source)

    kw = {} if delta is None else {"delta": delta}
    times = []
    res = None
    for rep in range(args.repeats):
        t0 = time.perf_counter()
        res = shortest_paths(g, source, engine=args.engine, mesh=mesh, **kw)
        times.append(time.perf_counter() - t0)
    best = min(times)
    n, m = g.n, (g.nnz if csr_native else args.edges)
    print(f"engine={args.engine} corpus={args.corpus} n={n} m={m} "
          f"procs={args.procs} time={best:.6f}s"
          + (f" sweeps={res.sweeps}" if res.sweeps is not None else "")
          + (f" edges_relaxed={res.edges_relaxed}"
             if res.edges_relaxed is not None else ""))

    if args.verify:
        adj = g.to_dense().adj if csr_native else g.adj   # O(n²): verify only
        ref, _ = dijkstra_serial_np(adj, args.source)
        got = res.dist[0] if res.dist.ndim == 2 else res.dist
        ok = np.allclose(np.where(np.isfinite(ref), ref, 1e30),
                         np.where(np.isfinite(got), got, 1e30), rtol=1e-5)
        print("verify:", "OK" if ok else "MISMATCH")
        if not ok:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
