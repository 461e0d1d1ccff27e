"""Process set-up shared by the entry points (``chip_smoke.py``, the
``repro.launch.sssp_*`` drivers, the calibration sweep and the benchmark
modules).  Nothing here runs on import: each entry point calls these
helpers itself, first thing in its ``main``, before any JAX operation.
"""
from __future__ import annotations

import os

import jax

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
CACHE_DIR = os.path.join(REPO, ".jax_cache")


def use_devices(n: int) -> list:
    """The first ``n`` devices of the default backend, for a run that asks
    for an ``n``-device mesh (``--devices`` / ``--procs``).

    On the CPU backend ``n`` host devices are emulated (the MPI ``-np``
    analogue), which must happen before JAX initializes its backends.  On
    an accelerator the setting touches only the unused CPU client, so the
    run gets ``n`` real devices or none: asking for more than are visible
    raises ``RuntimeError`` instead of running on fewer.
    """
    n = int(n)
    if n < 1:
        raise ValueError(f"need at least one device, got {n}")
    if n > 1:
        try:
            jax.config.update("jax_num_cpu_devices", n)
        except RuntimeError:
            pass    # backends already up: the visible count decides below
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"{n} devices requested but only {len(devices)} "
            f"{devices[0].platform} device(s) are visible")
    return devices[:n]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache goes to ``.jax_cache``
    at the root of the checkout: a fixed path, because the path is part of
    the cache key, so a directory that moves would never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
