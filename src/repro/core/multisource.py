"""Batched multi-source SSSP — beyond-paper extension (DESIGN.md §2).

The paper runs one source at a time.  The min-plus sweep generalizes to a
min-plus *matmul* over a (S, n) distance matrix: S sources amortize every
adjacency-tile load, raising arithmetic intensity S× — the adjacency matrix
is the memory traffic (see EXPERIMENTS.md §Roofline for the term-by-term
account).  Fixpoint and per-source results are identical to running the
paper's Alg. 3 S times.

``sssp_multisource_sharded`` distributes the sweep over a mesh axis with one
all-gather of the (S, loc_n) block per sweep — the batched version of the
one-collective-per-sweep fix for the paper's §V.2 synchronization diagnosis.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.core._axes import axis_size, varying

INF = jnp.inf


def relax_sweep_multi_ref(D: jax.Array, adj: jax.Array) -> jax.Array:
    return jnp.minimum(D, jnp.min(D[:, :, None] + adj[None, :, :], axis=1))


def init_dist(n: int, sources: jax.Array, dtype=jnp.float32) -> jax.Array:
    """(S, n) initial distance matrix: 0 at (s, sources[s]), INF elsewhere."""
    s = sources.shape[0]
    cols = jnp.arange(n, dtype=sources.dtype)[None, :]
    return jnp.where(cols == sources[:, None], 0.0, INF).astype(dtype)


@functools.partial(jax.jit, static_argnames=("sweep_fn", "max_sweeps"))
def sssp_multisource(
    adj: jax.Array,
    sources: jax.Array,
    *,
    sweep_fn: Optional[Callable] = None,
    max_sweeps: int | None = None,
):
    """Fixpoint SSSP from S sources at once.  Returns (D (S, n), sweeps)."""
    n = adj.shape[0]
    cap = n if max_sweeps is None else max_sweeps
    sweep = sweep_fn or relax_sweep_multi_ref
    D0 = init_dist(n, sources, adj.dtype)

    def cond(c):
        D, prev, it = c
        return (it < cap) & jnp.any(D != prev)

    def body(c):
        D, _, it = c
        new = jnp.minimum(sweep(D, adj), D)
        return new, D, it + 1

    prev0 = jnp.full_like(D0, -1.0)
    D, _, sweeps = lax.while_loop(cond, body, (D0, prev0, jnp.int32(0)))
    return D, sweeps


def sssp_multisource_sharded(
    adj_padded: jax.Array,
    sources: jax.Array,
    mesh: jax.sharding.Mesh,
    *,
    axis: str = "data",
    max_sweeps: int | None = None,
):
    """Distributed batched fixpoint: columns sharded, D replicated.

    One ``all_gather`` of (S, loc_n) per sweep.  Returns (D (S, n_pad), sweeps).
    """
    nprocs = axis_size(mesh, axis)
    n_pad = adj_padded.shape[0]
    assert n_pad % nprocs == 0
    loc_n = n_pad // nprocs
    s = sources.shape[0]
    cap = int(max_sweeps if max_sweeps is not None else n_pad)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, axis), P()),
        out_specs=(P(None, axis), P()),
    )
    def run(adj_loc, srcs):
        my_p = lax.axis_index(axis)
        v_base = my_p * loc_n
        D0 = varying(init_dist(n_pad, srcs, adj_loc.dtype), axis)
        prev0 = varying(jnp.full((s, n_pad), -1.0, adj_loc.dtype), axis)

        def cond(c):
            D, prev, it = c
            return (it < cap) & jnp.any(D != prev)

        def body(c):
            D, _, it = c
            # (s, n_pad) x (n_pad, loc_n) min-plus -> (s, loc_n)
            loc_new = jnp.min(D[:, :, None] + adj_loc[None, :, :], axis=1)
            mine = lax.dynamic_slice_in_dim(D, v_base, loc_n, axis=1)
            loc_new = jnp.minimum(mine, loc_new)
            new = lax.all_gather(loc_new, axis, axis=1, tiled=True)
            return new, D, it + 1

        it0 = varying(jnp.int32(0), axis)
        D, _, sweeps = lax.while_loop(cond, body, (D0, prev0, it0))
        mine = lax.dynamic_slice_in_dim(D, v_base, loc_n, axis=1)
        return mine, lax.psum(sweeps, axis) // nprocs

    D, sweeps = run(adj_padded, jnp.asarray(sources, jnp.int32))
    return D, sweeps
