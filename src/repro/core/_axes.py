"""Axis and mesh helpers: every sharded SSSP engine accepts a single
mesh-axis name or a tuple of names (e.g. ("pod", "data", "model") to shard
columns over all 512 chips in the multi-pod dry-run)."""
from __future__ import annotations

import math

import jax
from jax import lax
from jax.sharding import AxisType


def axis_tuple(axis):
    return axis if isinstance(axis, tuple) else (axis,)


def axis_size(mesh, axis) -> int:
    return math.prod(mesh.shape[a] for a in axis_tuple(axis))


def varying(x, axis):
    """Mark a device-invariant value as varying over ``axis`` — what a
    ``while_loop`` carry inside ``shard_map`` needs when its body returns a
    per-device value.  Carries the body returns replicated (a ``psum``)
    stay invariant and must not be marked."""
    return lax.pcast(x, axis_tuple(axis), to="varying")


def make_mesh(axis_shapes, axis_names, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes.  jax defaults to Explicit axes,
    under which ``with_sharding_constraint`` only asserts; the callers
    here place arrays with ``NamedSharding`` / ``shard_map`` and let the
    constraints inside jit resolve, which is what Auto axes do."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)
