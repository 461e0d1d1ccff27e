"""Benchmark orchestrator: one module per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only table3,...]

Outputs CSVs under experiments/bench/ and prints a summary.  Roofline rows
come from the dry-run JSONs (run ``python -m repro.launch.dryrun --all``
to regenerate them).
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (fig23_size_sweep, roofline, table3_density,
                        table4_scaling, weak_scaling)

# table4 and weak run each process count in a child that needs the
# device, so they go before anything in this process touches JAX (a parent
# holding the chip would leave the children none).
BENCHES = {
    "table4": table4_scaling.run,
    "weak": weak_scaling.run,       # the experiment the paper couldn't run
    "table3": table3_density.run,
    "fig23": fig23_size_sweep.run,
    "roofline": roofline.run,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    args = ap.parse_args()
    names = (args.only.split(",") if args.only else list(BENCHES))
    names.sort(key=list(BENCHES).index)
    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()
    failures = 0
    for name in names:
        print(f"\n=== {name} ===", flush=True)
        t0 = time.time()
        try:
            BENCHES[name](args.quick)
            print(f"=== {name} done in {time.time() - t0:.1f}s ===",
                  flush=True)
        except Exception as e:
            failures += 1
            import traceback
            print(f"=== {name} FAILED: {e} ===")
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
