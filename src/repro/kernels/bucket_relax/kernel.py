"""Fused Pallas TPU kernel for the Δ-stepping light-bucket pull.

The Δ engine's inner loop (core/delta_stepping.py) runs, per pass,

    new[v] = min(dist[v], min_k(dist[light_ell_idx[v, k]] + light_ell_w[v, k]))
    go     = any((new < dist) & (new < hi))

over the padded light in-ELL.  The plain ELL kernel (kernels/csr_relax)
covers only the candidate min; this kernel fuses the row-min, the
self-distance fold and the in-bucket improvement flag that drives the
inner ``lax.while_loop``, so one pass through VMEM produces both the new
distance block and the loop-control bit.

The gather ``dist[light_ell_idx]`` runs in XLA (ops.py): Mosaic lowers
only 2-D gathers, and a resident distance vector would cap n at what VMEM
holds.  The kernel reads the gathered candidates and weights slot-major,
as (K, V) blocks — the physical layout XLA gives a narrow (V, K) array on
TPU — and each v-block's own distances through a (1, bv) block.  The
bucket limit ``hi`` is a scalar in SMEM.

Grid is (V//bv, K//bk) with K as the *last* axis: for a fixed v-block the
k-steps run sequentially on the core and accumulate with min — race-free by
construction, same as csr_relax.  Each v-block writes its improvement flag
broadcast over one (1, 128) lane tile; the caller OR-reduces them.
Elementwise comparisons are exact, so flag-from-kernel equals
flag-from-XLA and the engine's schedule is bitwise-unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FLAG_LANES = 128        # one lane tile per v-block flag


def _bucket_relax_kernel(dg_ref, w_ref, own_ref, hi_ref, out_ref, flag_ref):
    """Grid (V//bv, K//bk).  dg/w: (bk, bv) gathered source distances and
    weights; own: (1, bv) the block's current distances; hi: (1, 1) SMEM;
    out: (1, bv) min-accumulated across the sequential k-steps, folded
    with ``own`` at the last step; flag: (1, 128) int32, 1 iff any row of
    this v-block improved below hi."""
    k_step = pl.program_id(1)

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, jnp.inf)

    cand = jnp.min(dg_ref[...] + w_ref[...], axis=0, keepdims=True)
    out_ref[...] = jnp.minimum(out_ref[...], cand)

    @pl.when(k_step == pl.num_programs(1) - 1)
    def _finish():
        old = own_ref[...]
        new = jnp.minimum(old, out_ref[...])
        out_ref[...] = new
        imp = (new < old) & (new < hi_ref[0, 0])
        flag_ref[...] = jnp.full(flag_ref.shape, jnp.any(imp), jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("block_v", "block_k", "interpret")
)
def bucket_relax(
    dg: jax.Array,
    w: jax.Array,
    dist: jax.Array,
    hi: jax.Array,
    *,
    block_v: int = 256,
    block_k: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One fused light-bucket pull pass on slot-major operands.  Requires
    V % block_v == 0 and K % block_k == 0 (ops.py pads to the grid; padded
    rows carry INF distances and INF weights, so they neither improve nor
    flag).

    dg (K, V) gathered source distances, w (K, V), dist (V,), hi f32
    scalar -> (new_dist (V,), flags (V // block_v * 128,) int32).
    """
    K, V = dg.shape
    if block_k is None:
        block_k = K
    assert w.shape == (K, V) and dist.shape == (V,), (w.shape, dist.shape)
    assert V % block_v == 0 and K % block_k == 0, (V, K, block_v, block_k)
    grid = (V // block_v, K // block_k)
    blk = pl.BlockSpec((block_k, block_v), lambda v, k: (k, v))
    row = pl.BlockSpec((1, block_v), lambda v, k: (0, v))
    out, flags = pl.pallas_call(
        _bucket_relax_kernel,
        grid=grid,
        in_specs=[blk, blk, row,
                  pl.BlockSpec((1, 1), lambda v, k: (0, 0),
                               memory_space=pltpu.SMEM)],
        out_specs=[row,
                   pl.BlockSpec((1, FLAG_LANES), lambda v, k: (0, v))],
        out_shape=[
            jax.ShapeDtypeStruct((1, V), dist.dtype),
            jax.ShapeDtypeStruct((1, grid[0] * FLAG_LANES), jnp.int32),
        ],
        interpret=interpret,
    )(dg, w, dist[None, :], jnp.asarray(hi, dist.dtype).reshape(1, 1))
    return out[0], flags[0]
