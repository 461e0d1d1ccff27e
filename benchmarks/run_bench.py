"""Tracked SSSP benchmark gate — one repeatable runner for every engine.

Times the engines on the paper's Table I (dense) and Table II (sparse)
corpora and writes a single machine-diffable record, ``BENCH_sssp.json``,
so the perf trajectory has a baseline: CI runs ``--smoke`` and uploads the
artifact, and PRs that touch a hot path can diff their numbers against the
committed file.

Beyond wall time, every CSR-family engine reports its **edges relaxed**
(``SsspResult.edges_relaxed``): ``bellman_csr`` relaxes all nnz arcs every
sweep, the frontier engine counts actual frontier out-degrees.  The
``gate`` section asserts the frontier engine relaxes strictly fewer edges
per solve than ``bellman_csr`` on every Table II point with n >= 10000 —
the measurable form of the paper's §V "every edge, every sweep" complaint
being fixed.

The Δ-stepping engine gets its own corpora — the road-like grid and the
skewed-hub heavy-tail generators (core/csr.py) whose shapes it exists
for — and its own ``gate_delta``: on every such point with n >= 10000,
``delta_stepping`` must finish in strictly fewer bucket phases than the
frontier engine takes sweeps AND in less wall-clock time.  Smoke runs
never reach that size, so they gate the phase count only (tiny-graph
wall-clock is jit-dispatch noise) and say so in the recorded rule.

Correctness rides along: per corpus point all engines' distances must
agree bitwise with the first engine run (min-plus over f32 path sums is
exact, so agreement is exact equality, not allclose).

    PYTHONPATH=src python -m benchmarks.run_bench [--smoke | --full]
                                                  [--out PATH] [--repeats N]
                                                  [--devices P]

``--smoke`` caps every corpus for CI (< ~1 min on CPU); ``--full`` extends
the sparse corpus to the paper's 40,000-vertex ceiling point.  ``--devices
P`` (default 4) adds the vertex-partitioned sharded CSR engines on a
P-device mesh — emulated host devices on CPU, the MPI-procs analogue;
``--devices 1`` drops the sharded leg.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time

import numpy as np

import jax

from benchmarks.common import REPO, time_engine
from repro.core import csr as C
from repro.core import graph as G
from repro.core.api import shortest_paths
from repro.launch.runtime import enable_compile_cache, use_devices

DEFAULT_OUT = os.path.join(REPO, "BENCH_sssp.json")

# per-engine n ceilings: the O(n²)-total serial loop and the interpret-mode
# Pallas kernels (CPU: python per grid step) get tighter caps so the run
# stays repeatable in minutes; on real TPU the kernel caps can be lifted.
ENGINE_CAPS = {
    "serial": 2000,
    "bellman": 2000,              # dense matrix: the paper's own ceiling
    "bellman_kernel": 1000,
    "bellman_csr": None,
    "bellman_csr_kernel": 1000,
    "frontier": None,
    "frontier_kernel": 1000,
    "delta_stepping": None,
    "delta_stepping_kernel": 1000,
    "multisource_csr": None,
    # sharded CSR engines: pure-XLA shard_map, no Pallas interpret cost,
    # and the compiled fixpoint is memoized per (mesh, shapes)
    # (core/sharded_csr._build_*), so repeat solves don't re-trace.
    "bellman_csr_sharded": None,
    "frontier_sharded": None,
}
SMOKE_CAPS = {k: 1000 if v is None else 100 for k, v in ENGINE_CAPS.items()}

DENSE_ENGINES = ("serial", "bellman", "bellman_kernel",
                 "bellman_csr", "frontier")
SPARSE_ENGINES = ("serial", "bellman", "bellman_csr", "bellman_csr_kernel",
                  "frontier", "frontier_kernel", "multisource_csr")
SHARDED_CSR = ("bellman_csr_sharded", "frontier_sharded")
# Δ-leg: the engines raced on the road/hub corpora (gate_delta compares
# the first two; the kernel engine rides along under its interpret cap).
DELTA_ENGINES = ("frontier", "delta_stepping", "delta_stepping_kernel")
DELTA_NS = (10000, 20000)         # gate-sized points (>= gate_delta min_n)
DELTA_NS_SMOKE = (1000,)

N_SOURCES = 4                     # batch width for multisource_csr


def _bench_point(corpus: str, n: int, m: int, engines, caps, repeats,
                 mesh=None):
    """Run every applicable engine on one corpus point; returns records."""
    cg = C.random_csr_graph(n, m, seed=n + m)
    g = cg.to_dense() if n <= 2000 else None      # dense engines' input
    srcs = np.linspace(0, n - 1, N_SOURCES).astype(np.int32)
    procs = mesh.devices.size if mesh is not None else 1
    records, anchor = [], None
    for engine in engines:
        cap = caps.get(engine)
        if cap is not None and n > cap:
            continue
        needs_dense = engine in ("serial", "bellman", "bellman_kernel")
        if needs_dense and g is None:
            continue
        sharded = engine in SHARDED_CSR
        if sharded and mesh is None:
            continue
        arg = g if needs_dense else cg
        src = srcs if engine == "multisource_csr" else 0
        kw = {"mesh": mesh} if sharded else {}
        res = shortest_paths(arg, src, engine=engine, **kw)  # warm + verify
        t = time_engine(
            lambda: shortest_paths(arg, src, engine=engine, **kw),
            repeats=repeats, warmup=0,     # the verify run already warmed jit
        )
        d0 = res.dist[0] if res.dist.ndim == 2 else res.dist
        if anchor is None:
            anchor = d0
            agree = True
        else:
            agree = bool(np.array_equal(anchor, d0))     # bitwise, see above
        rec = {
            "corpus": corpus, "n": n, "m": m, "nnz": cg.nnz,
            "engine": engine, "time_s": round(t, 6),
            "sweeps": res.sweeps, "edges_relaxed": res.edges_relaxed,
            "sources": N_SOURCES if engine == "multisource_csr" else 1,
            "procs": procs if sharded else 1,
            "agrees_bitwise": agree,
        }
        records.append(rec)
        per_src = t / rec["sources"]
        tag = f"{engine}@P{procs}" if sharded else engine
        print(f"  {corpus} n={n:6d} {tag:18s} {per_src:9.5f}s/src "
              f"sweeps={res.sweeps} edges={res.edges_relaxed}", flush=True)
    return records


def _bench_delta_point(corpus: str, n: int, caps, repeats):
    """One road/hub corpus point raced across DELTA_ENGINES.  Same record
    shape as _bench_point; ``sweeps`` for the Δ engines counts OUTER
    bucket phases (see core/delta_stepping.py), the number gate_delta
    compares against the frontier sweep count."""
    make = (C.road_like_csr_graph if corpus == "road"
            else C.skewed_hub_csr_graph)
    cg = make(n, seed=n)
    records, anchor = [], None
    for engine in DELTA_ENGINES:
        cap = caps.get(engine)
        if cap is not None and cg.n > cap:
            continue
        res = shortest_paths(cg, 0, engine=engine)   # warm + verify
        t = time_engine(
            lambda: shortest_paths(cg, 0, engine=engine),
            repeats=repeats, warmup=0,
        )
        if anchor is None:
            anchor, agree = res.dist, True
        else:
            agree = bool(np.array_equal(anchor, res.dist))
        records.append({
            "corpus": corpus, "n": cg.n, "m": cg.nnz, "nnz": cg.nnz,
            "engine": engine, "time_s": round(t, 6),
            "sweeps": res.sweeps, "edges_relaxed": res.edges_relaxed,
            "sources": 1, "procs": 1, "agrees_bitwise": agree,
        })
        print(f"  {corpus} n={cg.n:6d} {engine:18s} {t:9.5f}s/src "
              f"sweeps={res.sweeps} edges={res.edges_relaxed}", flush=True)
    return records


def _gate(results, min_n: int = 10000):
    """Frontier must relax strictly fewer edges than bellman_csr per solve
    on every sparse point with n >= min_n (smoke runs gate whatever sparse
    points they have, so the check never silently vanishes)."""
    by_point = {}
    for r in results:
        if r["corpus"] == "sparse" and r["engine"] in ("bellman_csr",
                                                       "frontier"):
            by_point.setdefault(r["n"], {})[r["engine"]] = r
    pts, have_target = [], False
    for n in sorted(by_point):
        pair = by_point[n]
        if "bellman_csr" not in pair or "frontier" not in pair:
            continue
        fe = pair["frontier"]["edges_relaxed"]
        be = pair["bellman_csr"]["edges_relaxed"]
        counted = n >= min_n
        have_target = have_target or counted
        pts.append({
            "n": n, "m": pair["frontier"]["m"],
            "frontier_edges": fe, "bellman_csr_edges": be,
            "edge_ratio": round(fe / be, 4) if be else None,
            "frontier_fewer": fe < be,
            "counted": counted,
        })
    counted = [p for p in pts if (p["counted"] if have_target else True)]
    if have_target:
        rule = (f"frontier relaxes strictly fewer edges than bellman_csr "
                f"on every sparse point with n >= {min_n}")
    else:
        # smoke-sized corpora never reach min_n; say what was checked so
        # the artifact can't be read as covering the full-run criterion.
        rule = (f"frontier relaxes strictly fewer edges than bellman_csr "
                f"on every available sparse point (none with n >= {min_n} "
                f"in this run)")
    return {
        "rule": rule,
        "points": pts,
        "pass": bool(counted) and all(p["frontier_fewer"] for p in counted),
    }


def _gate_sharded(results):
    """frontier_sharded must relax NO MORE edges than the single-device
    frontier engine on every sparse point where both ran — the partition
    assigns each arc exactly one owner, so the psum of per-owner counters
    equals the single-device counter; any excess means the exchange is
    re-relaxing arcs.  Absent when no sharded leg ran (--devices 1)."""
    by_point = {}
    for r in results:
        if r["corpus"] == "sparse" and r["engine"] in ("frontier",
                                                       "frontier_sharded"):
            by_point.setdefault(r["n"], {})[r["engine"]] = r
    pts = []
    for n in sorted(by_point):
        pair = by_point[n]
        if "frontier" not in pair or "frontier_sharded" not in pair:
            continue
        fe = pair["frontier"]["edges_relaxed"]
        se = pair["frontier_sharded"]["edges_relaxed"]
        pts.append({
            "n": n, "m": pair["frontier_sharded"]["m"],
            "procs": pair["frontier_sharded"]["procs"],
            "frontier_sharded_edges": se, "frontier_edges": fe,
            "no_more": se <= fe,
        })
    if not pts:
        return None
    procs = pts[0]["procs"]
    return {
        "rule": (f"frontier_sharded at P={procs} relaxes no more edges than "
                 "single-device frontier on every shared sparse point "
                 "(same work, partitioned)"),
        "points": pts,
        "pass": all(p["no_more"] for p in pts),
    }


def _gate_delta(results, min_n: int = 10000):
    """Δ-stepping must beat the frontier engine where it claims to: on
    every road/hub point with n >= min_n it needs strictly fewer bucket
    phases than the frontier engine takes sweeps AND strictly less
    wall-clock.  Runs too small to have a counted point (smoke) gate the
    phase count only — jit dispatch dominates tiny wall-clocks — and the
    recorded rule says so, mirroring _gate's honesty convention."""
    by_point = {}
    for r in results:
        if r["corpus"] in ("road", "hub") and r["engine"] in (
                "frontier", "delta_stepping"):
            by_point.setdefault((r["corpus"], r["n"]), {})[r["engine"]] = r
    pts, have_target = [], False
    for key in sorted(by_point):
        pair = by_point[key]
        if "frontier" not in pair or "delta_stepping" not in pair:
            continue
        f, d = pair["frontier"], pair["delta_stepping"]
        counted = key[1] >= min_n
        have_target = have_target or counted
        pts.append({
            "corpus": key[0], "n": key[1], "m": f["m"],
            "delta_phases": d["sweeps"], "frontier_sweeps": f["sweeps"],
            "delta_time_s": d["time_s"], "frontier_time_s": f["time_s"],
            "fewer_sweeps": d["sweeps"] < f["sweeps"],
            "faster": d["time_s"] < f["time_s"],
            "counted": counted,
        })
    if not pts:
        return None
    if have_target:
        counted_pts = [p for p in pts if p["counted"]]
        ok = all(p["fewer_sweeps"] and p["faster"] for p in counted_pts)
        rule = (f"delta_stepping takes strictly fewer bucket phases than "
                f"frontier sweeps AND less wall-clock on every road/hub "
                f"point with n >= {min_n}")
    else:
        ok = all(p["fewer_sweeps"] for p in pts)
        rule = (f"delta_stepping takes strictly fewer bucket phases than "
                f"frontier sweeps on every available road/hub point "
                f"(none with n >= {min_n} in this run; wall-clock not "
                f"gated at smoke sizes)")
    return {"rule": rule, "points": pts, "pass": ok}


def run(smoke: bool = False, full: bool = False, repeats: int = 3,
        out: str = DEFAULT_OUT, devices: int = 1,
        cost_out=None) -> str:
    cost_log = None
    if cost_out:
        # every bench solve goes through core.api.shortest_paths, whose
        # observability shim emits one cost record per solve into the
        # installed log (repro/obs/profile.py)
        from repro.obs import CostLog, set_cost_log
        cost_log = CostLog()
        set_cost_log(cost_log)
    caps = SMOKE_CAPS if smoke else ENGINE_CAPS
    dense_cap = 100 if smoke else 2000
    sparse_cap = 1000 if smoke else (40000 if full else 20000)
    mesh = None
    if devices > 1:
        from repro.core._axes import make_mesh
        mesh = make_mesh((devices,), ("data",), devices=use_devices(devices))
    sparse_engines = SPARSE_ENGINES + (SHARDED_CSR if mesh is not None else ())
    results = []
    for n, m in G.PAPER_DENSE:
        if n <= dense_cap:
            results += _bench_point("dense", n, m, DENSE_ENGINES,
                                    caps, repeats)
    for n, m in G.PAPER_SPARSE:
        if n <= sparse_cap:
            results += _bench_point("sparse", n, m, sparse_engines,
                                    caps, repeats, mesh=mesh)
    for corpus in ("road", "hub"):
        for n in (DELTA_NS_SMOKE if smoke else DELTA_NS):
            results += _bench_delta_point(corpus, n, caps, repeats)
    gate = _gate(results)
    gate_sharded = _gate_sharded(results)
    gate_delta = _gate_delta(results)
    doc = {
        "schema": 2,
        "meta": {
            "created_unix": int(time.time()),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "smoke": smoke, "full": full, "repeats": repeats,
            "devices": devices,
        },
        "results": results,
        "gate": gate,
        "gate_sharded": gate_sharded,
        "gate_delta": gate_delta,
    }
    bad = [r for r in results if not r["agrees_bitwise"]]
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"\nwrote {len(results)} records to {out}")
    if cost_log is not None:
        from repro.obs import set_cost_log
        from repro.obs.validate import validate_cost_records
        set_cost_log(None)
        errs = validate_cost_records([r.to_dict() for r in cost_log.records])
        if errs:
            raise SystemExit(f"cost records invalid: {errs[:5]}")
        cost_log.write_jsonl(cost_out)
        print(f"wrote {len(cost_log.records)} cost records to {cost_out}")
    if bad:
        raise SystemExit(
            f"bitwise disagreement in {[(r['n'], r['engine']) for r in bad]}"
        )
    from benchmarks.gates import enforce
    enforce(doc)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized corpora (< ~1 min on CPU)")
    ap.add_argument("--full", action="store_true",
                    help="extend sparse corpus to the paper's n=40000")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--devices", type=int, default=4,
                    help="mesh size for the sharded CSR engines (emulated "
                         "host devices on CPU); 1 drops the leg")
    ap.add_argument("--cost-out", default=None, metavar="PATH",
                    help="write one per-solve cost record per engine call "
                         "as JSONL (repro/obs/profile.py schema)")
    args = ap.parse_args()
    enable_compile_cache()
    use_devices(args.devices)
    run(args.smoke, args.full, repeats=args.repeats, out=args.out,
        devices=args.devices, cost_out=args.cost_out)
