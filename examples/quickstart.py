"""Quickstart: the paper's three SSSP engines and a multi-source batch
through the public API, in under a minute on CPU.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import graph as G
from repro.core.api import shortest_paths

# --- 1. SSSP: serial (Alg.1), fixpoint (Alg.3/4), Pallas kernel ----------
g = G.random_graph(500, 1500, seed=0)
print(f"graph: {g.n} vertices, {g.num_edges} edges")

for engine in ("serial", "bellman", "bellman_kernel"):
    res = shortest_paths(g, source=0, engine=engine)
    reached = int(np.isfinite(res.dist).sum())
    extra = f", {res.sweeps} sweeps" if res.sweeps is not None else ""
    print(f"  {engine:16s}: reached {reached}/{g.n}{extra}; "
          f"max dist {np.nanmax(np.where(np.isfinite(res.dist), res.dist, np.nan)):.2f}")

# --- 2. multi-source batching (beyond-paper) ------------------------------
res = shortest_paths(g, np.array([0, 7, 99]), engine="multisource")
print(f"  multisource     : dist matrix {res.dist.shape}, {res.sweeps} sweeps")

print("\nquickstart OK")
