"""The control of the correctness check: the reference computed one
precision below the configuration's (bfloat16 for float32), put in the
program's place and judged by the same comparison a run makes.  It has to
come out wrong; its count is the upper reading that the check's limit lies
below.  Benchmark runs never run it.

    python3 bench/control.py --workload tableii-40k.batch --seeds 1 2 3 \
        --jobs 400

Each seed draws the graph and the jobs as a run of that seed does, takes
the first ``--jobs`` jobs (about as many as a window answers) and
compares the answers of the sources a run of that seed would compare.
Prints one JSON line per seed.  Needs no chip: the program is not
involved.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_reading(cell, seed: int, jobs: int, root: str) -> dict:
    from bench import graphs, harness, traffic

    gen = graphs.generator(cell.config["generator"], root)
    csrs = [graphs.build(cell.config, seed, g, gen)
            for g in range(cell.mix["graphs"])]
    _, drawn = traffic.make_jobs(cell.mix, csrs, seed)
    queries = [(job.graph, s, t) for i in range(jobs)
               for job in [drawn[i % len(drawn)]] for s, t in job.queries]
    picked = harness.picked_sources(queries, seed,
                                    cell.mix["check_sources"])
    queries = [q for q in queries if q[:2] in set(picked)]
    rows = harness.reference_rows(csrs, cell.config, seed, picked, root,
                                  precision="bf16")
    got = [rows[g, s] if t is None else rows[g, s][t]
           for g, s, t in queries]
    wrong, compared = harness.check(csrs, cell.config, seed, queries, got,
                                    picked, root)
    return {"workload": cell.name, "seed": seed, "compared": compared,
            "wrong_answers": wrong, "limit": 0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--jobs", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    for seed in args.seeds:
        print(json.dumps(control_reading(cell, seed, args.jobs, ROOT)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
