"""Shared benchmark utilities: timing envelope per the paper's §III."""
from __future__ import annotations

import csv
import os
import subprocess
import sys
import time
from typing import Callable

OUT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "experiments", "bench")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def time_engine(fn: Callable, *, repeats: int = 3, warmup: int = 1) -> float:
    """Best-of-N wall time (the paper reports single-run chrono timings;
    best-of-N with warmup removes jit compilation like the paper excludes
    graph construction)."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def write_csv(name: str, header: list[str], rows: list[list]) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, name)
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)
    return path


def run_module(module: str, args: list[str], timeout: int = 900) -> str:
    """Run a repro module in a subprocess and return its stdout.  The
    caller must not have touched JAX: on an accelerator the child needs
    the devices.  ``repro.launch.sssp_run --procs P`` sets up its own P
    devices (emulated host devices on the CPU backend)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-m", module, *args],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"{module} failed:\n{r.stdout}\n{r.stderr}")
    return r.stdout
