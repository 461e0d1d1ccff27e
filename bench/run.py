"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json`` at the root of the
checkout; its configuration and traffic mix are files under ``bench/``
found by name.  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window's first jobs.  The last line on standard
output is the result, one JSON object; everything else goes to standard
error.  Without a TPU, or with fewer chips than the cell asks for, the
run exits 2 and prints no result.
"""
import time

T0 = time.perf_counter()    # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness

    return harness.main(args.workload, args.seed, args.seconds,
                        bool(args.trace), t0=T0, root=ROOT)


if __name__ == "__main__":
    sys.exit(main())
