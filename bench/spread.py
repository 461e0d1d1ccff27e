"""Run one cell on several seeds, one process after another, and report
each metric's median and spread (interquartile distance over the median).

    python3 bench/spread.py --workload tableii-40k.p2p --seeds 11 12 13 \
        --out runs.jsonl [--seconds 10] [--trace 0]

This process never imports JAX, so each run gets the chip to itself.  Each
run's result line, with the seed, its wall time and its exit code, is
appended to ``--out``; the end of each run's standard error is printed.
A bound for a metric is about five times the wider spread of two such
sets on the same seeds.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    t = time.perf_counter()
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t
    lines = p.stdout.strip().splitlines()
    rec = {"workload": workload, "seed": seed, "trace": trace,
           "rc": p.returncode, "wall_s": wall}
    if p.returncode == 0 and lines:
        rec["result"] = json.loads(lines[-1])
    sys.stderr.write(f"--- {workload} seed {seed} trace {trace} rc "
                     f"{p.returncode} wall {wall:.1f} s\n"
                     + "\n".join(p.stderr.strip().splitlines()[-12:]) + "\n")
    return rec


def summary(recs: list) -> dict:
    values: dict = {}
    for r in recs:
        for k, m in r.get("result", {}).get("metrics", {}).items():
            values.setdefault(k, []).append(m["value"])
    out = {}
    for k, v in sorted(values.items()):
        med = statistics.median(v)
        row = {"n": len(v), "median": med}
        if len(v) >= 2:
            q1, _, q3 = statistics.quantiles(v, n=4)
            row["spread"] = (q3 - q1) / med if med else None
        out[k] = row
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window (default: run_seconds of BENCHMARK.json)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True,
                    help="JSON lines file the runs are appended to")
    args = ap.parse_args(argv)
    if args.seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            args.seconds = json.load(f)["run_seconds"]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    recs = []
    for seed in args.seeds:
        rec = run_one(args.workload, seed, args.seconds, args.trace)
        recs.append(rec)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    bad = [r["seed"] for r in recs
           if r["rc"] != 0 or not r.get("result", {}).get("correct")]
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "not_correct": bad, "metrics": summary(recs)}),
          flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
