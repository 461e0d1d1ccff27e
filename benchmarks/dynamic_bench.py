"""Tracked dynamic-graph benchmark gate — incremental repair vs re-solve.

The dynamic subsystem's whole bet (dynamic/repair.py, after
arXiv:1505.05033's slowly-changing-graph regime) is that repairing an
existing fixpoint after a small mutation batch beats re-solving from
scratch.  This benchmark measures that bet on the paper's sparse corpus
shape (m = 3n) and writes the comparison to ``BENCH_dynamic.json``:

per mutation-batch size B in {1, 8}: starting from a solved source row,
apply ROUNDS seeded mutation batches (add / delete / weight-update mixed,
both repair directions) and after each batch time

* ``repair_sssp``  — the incremental repair, chained (each round repairs
  the previous round's result), and
* ``sssp_frontier_dynamic`` — a full frontier re-solve on the same
  committed operands (the fairest from-scratch baseline: same sweep,
  same staged arrays, warm jit),

asserting the two are **bitwise-equal every round**.  Steady state =
medians over the counted rounds (warmup rounds compile and are
discarded).

The ``gate`` asserts, per batch size:

* repair relaxes STRICTLY fewer edges than the full re-solve (medians of
  the engines' own ``edges_relaxed`` counters — comparable by
  construction: both count base-arc relax slots), and
* repair is >= ``min_ratio`` x faster steady-state (2.0 at the full
  n=10000 scale; 1.2 for smoke-sized corpora where fixed overheads
  dominate).

    PYTHONPATH=src python -m benchmarks.dynamic_bench [--smoke]
                                                      [--out PATH]

Spliced into EXPERIMENTS.md §Dynamic bench by
benchmarks/make_experiments_md.py; CI runs ``--smoke`` and uploads the
JSON (workflow job ``dynamic-smoke``).
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time

import numpy as np

import jax

from benchmarks.common import REPO
from repro.core import csr as C
from repro.dynamic import DynamicGraph, repair_sssp, solve_dynamic
from repro.serve.workload import EdgeChurn

DEFAULT_OUT = os.path.join(REPO, "BENCH_dynamic.json")

BATCH_SIZES = (1, 8)
ROUNDS = 12            # counted rounds per batch size
WARMUP = 2             # discarded (jit compile + cache settling)
SOURCE = 0
OVERLAY_CAPACITY = 512  # > ROUNDS * max batch: no mid-measurement compaction


def _apply_batch(dyn: DynamicGraph, churn: EdgeChurn, size: int) -> None:
    """One mutation batch: ``size`` edits sampled by the shared churn
    sampler (serve/workload.py — same distribution as the churn traces)
    applied directly to the overlay."""
    for _ in range(size):
        op, u, v, w = churn.sample()
        dyn.apply((op, u, v) if w is None else (op, u, v, w))


def run_batch_size(n: int, B: int, seed: int) -> dict:
    cg = C.random_csr_graph(n, 3 * n, seed=seed)
    dyn = DynamicGraph(cg, overlay_capacity=OVERLAY_CAPACITY)
    churn = EdgeChurn(dyn.base, np.random.default_rng(seed + 1))
    prev = solve_dynamic(dyn, SOURCE)
    t_rep, t_full, e_rep, e_full, cones = [], [], [], [], []
    for rnd in range(WARMUP + ROUNDS):
        _apply_batch(dyn, churn, B)
        batch = dyn.commit()
        t0 = time.perf_counter()
        res, stats = repair_sssp(dyn, prev, batch)
        dt_rep = time.perf_counter() - t0
        t0 = time.perf_counter()
        full = solve_dynamic(dyn, SOURCE)
        dt_full = time.perf_counter() - t0
        if not (np.array_equal(res.dist, full.dist)
                and np.array_equal(res.pred, full.pred)):
            raise SystemExit(
                f"repair != full re-solve at n={n} B={B} round {rnd}")
        prev = res
        if rnd >= WARMUP:
            t_rep.append(dt_rep)
            t_full.append(dt_full)
            e_rep.append(res.edges_relaxed)
            e_full.append(full.edges_relaxed)
            cones.append(stats.cone)
            from repro.obs import get_cost_log
            cl = get_cost_log()
            if cl.enabled:
                # the dynamic engines bypass core.api's shim — emit the
                # measured rounds directly (one repair + one full solve)
                m_live = int(dyn.nnz_live)
                cl.emit(engine="repair", n=n, m=m_live,
                        sweeps=res.sweeps or 0,
                        edges_relaxed=res.edges_relaxed or 0,
                        wall_ms=dt_rep * 1e3,
                        converged=res.converged is not False, batch=B)
                cl.emit(engine="frontier_dynamic", n=n, m=m_live,
                        sweeps=full.sweeps or 0,
                        edges_relaxed=full.edges_relaxed or 0,
                        wall_ms=dt_full * 1e3,
                        converged=full.converged is not False, batch=B)
    med = lambda xs: float(np.median(xs))
    rec = {
        "n": n, "m": 3 * n, "batch_edges": B, "rounds": ROUNDS,
        "repair_time_s": round(med(t_rep), 6),
        "full_time_s": round(med(t_full), 6),
        "speedup": round(med(t_full) / med(t_rep), 3),
        "repair_edges": int(med(e_rep)),
        "full_edges": int(med(e_full)),
        "edge_ratio": round(med(e_rep) / max(med(e_full), 1), 5),
        "cone_median": int(med(cones)),
        "verified_bitwise_vs_full": True,
    }
    print(f"  n={n} B={B}: repair {rec['repair_time_s'] * 1e3:8.2f} ms "
          f"({rec['repair_edges']:>8d} edges, cone {rec['cone_median']}) "
          f"vs full {rec['full_time_s'] * 1e3:8.2f} ms "
          f"({rec['full_edges']:>8d} edges) -> {rec['speedup']:.2f}x",
          flush=True)
    return rec


def run(smoke: bool = False, out: str = DEFAULT_OUT,
        cost_out=None) -> str:
    cost_log = None
    if cost_out:
        from repro.obs import CostLog, set_cost_log
        cost_log = CostLog()
        set_cost_log(cost_log)
    n = 1000 if smoke else 10000
    records = [run_batch_size(n, B, seed=n + B) for B in BATCH_SIZES]
    min_ratio = 2.0 if n >= 10000 else 1.2
    points = []
    ok = True
    for r in records:
        fewer = r["repair_edges"] < r["full_edges"]
        fast = r["speedup"] >= min_ratio
        points.append({
            "batch_edges": r["batch_edges"],
            "repair_edges": r["repair_edges"],
            "full_edges": r["full_edges"],
            "repair_fewer": fewer,
            "speedup": r["speedup"],
            "fast_enough": fast,
        })
        ok = ok and fewer and fast
    gate = {
        "rule": (f"per mutation-batch size in {list(BATCH_SIZES)} at sparse "
                 f"n={n}: incremental repair relaxes strictly fewer edges "
                 f"than a full frontier re-solve and is >= {min_ratio}x "
                 "faster steady-state (medians, bitwise-verified rounds)"),
        "min_ratio": min_ratio,
        "points": points,
        "pass": bool(ok),
    }
    doc = {
        "schema": 1,
        "meta": {
            "created_unix": int(time.time()),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "smoke": smoke,
            "rounds": ROUNDS, "warmup": WARMUP,
            "overlay_capacity": OVERLAY_CAPACITY, "source": SOURCE,
        },
        "results": records,
        "gate": gate,
    }
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"\nwrote {len(records)} batch-size records to {out}")
    if cost_log is not None:
        from repro.obs import set_cost_log
        from repro.obs.validate import validate_cost_records
        set_cost_log(None)
        errs = validate_cost_records([r.to_dict() for r in cost_log.records])
        if errs:
            raise SystemExit(f"cost records invalid: {errs[:5]}")
        cost_log.write_jsonl(cost_out)
        print(f"wrote {len(cost_log.records)} cost records to {cost_out}")
    from benchmarks.gates import enforce
    enforce(doc)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized corpus (n=1000)")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--cost-out", default=None, metavar="PATH",
                    help="write per-round repair/full cost records as "
                         "JSONL (repro/obs/profile.py schema)")
    args = ap.parse_args()
    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()
    run(args.smoke, out=args.out, cost_out=args.cost_out)
