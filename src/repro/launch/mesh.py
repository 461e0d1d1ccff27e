"""Mesh construction.  Functions, not module-level constants, so importing
this module never touches jax device state (the dry-run must set
XLA_FLAGS before the first jax device query)."""
from __future__ import annotations

import jax

from repro.core._axes import make_mesh


def _mk(shape, axes):
    return make_mesh(shape, axes)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds a leading pod=2 axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Mesh over whatever devices exist (tests / smoke runs)."""
    n = jax.device_count()
    if data is None:
        data = n // model
    assert data * model <= n, (data, model, n)
    return _mk((data, model), ("data", "model"))
