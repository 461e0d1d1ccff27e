"""Pallas TPU kernel for padded-ELL sparse relaxation.

The dense kernel (kernels/sssp_relax) streams the whole n² matrix through
VMEM per sweep; for Table II graphs that is ~333x more data than the edges
justify.  This kernel instead tiles the **padded-ELL** edge layout
(core/csr.py): fixed-width rows of (source index, weight) pairs, so block
shapes stay static — the same role the paper's vertex padding plays for its
process grid (§III-B.2).

    out[v] = min_k ( dg[k, v] + w[k, v] ),   dg[k, v] = dist[ell_idx[v, k]]

The arbitrary-index gather ``dist[ell_idx]`` runs in XLA (ops.py): Mosaic
lowers only 2-D gathers, and a resident distance vector would cap n at
what VMEM holds.  The kernel takes the gathered candidates and weights
slot-major, as (K, n): that is the physical layout XLA already gives a
narrow (n, K) array on TPU, so the transpose is free, and the row-min is
a reduction over sublanes that leaves each v-block's result lane-dense.

Grid is (n//bv, K//bk) with K as the *last* axis: for a fixed v-block the
k-steps run sequentially on the core and accumulate with min — race-free by
construction, the same atomicMin replacement argument as the dense kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ell_relax_kernel(dg_ref, w_ref, out_ref):
    """Grid (n//bv, K//bk).  dg/w: (bk, bv) gathered source distances and
    weights; out: (1, bv), min-accumulated across the sequential k-steps."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, jnp.inf)

    cand = jnp.min(dg_ref[...] + w_ref[...], axis=0, keepdims=True)
    out_ref[...] = jnp.minimum(out_ref[...], cand)


@functools.partial(
    jax.jit, static_argnames=("block_v", "block_k", "interpret")
)
def ell_relax(
    dg: jax.Array,
    w: jax.Array,
    *,
    block_v: int = 256,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """min_k(dg[k, v] + w[k, v]) for all v, on slot-major (K, n) operands.
    Requires n % block_v == 0 and K % block_k == 0 (ops.py pads to the
    grid).

    Returns the pure relaxation term; callers take ``jnp.minimum(dist, ·)``
    (kept outside so XLA fuses it into the surrounding while_loop body).
    """
    K, n = dg.shape
    if block_k is None:
        block_k = K
    assert w.shape == (K, n), (w.shape, dg.shape)
    assert n % block_v == 0 and K % block_k == 0, (n, K, block_v, block_k)
    spec = pl.BlockSpec((block_k, block_v), lambda v, k: (k, v))
    out = pl.pallas_call(
        _ell_relax_kernel,
        grid=(n // block_v, K // block_k),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((1, block_v), lambda v, k: (0, v)),
        out_shape=jax.ShapeDtypeStruct((1, n), dg.dtype),
        interpret=interpret,
    )(dg, w)
    return out[0]
