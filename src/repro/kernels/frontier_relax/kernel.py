"""Pallas TPU kernel for frontier-compacted candidate generation.

The frontier engine (core/frontier.py) relaxes only the active vertices'
out-edges.  The streaming half of that sweep — add each compacted frontier
vertex's distance across its padded out-ELL window — is dense, regular
work over (K, F) blocks, and that is what this kernel owns:

    cand[k, f] = df[f] + w[k, f]

The gather of the frontier distances ``df = dist[fids]`` (INF past the
compaction sentinel) runs in XLA (ops.py): Mosaic lowers only 2-D
gathers, and a resident distance vector would cap n at what VMEM holds.
The scatter-min of ``cand`` into the destination vertices stays outside
in XLA too (``.at[].min``): TPU Pallas has no scatter primitive, and XLA's
native deterministic scatter lowering is exactly the associative
``atomicMin`` replacement the other engines already rely on.

Operands are slot-major, (K, F) — the physical layout XLA gives a narrow
(F, K) array on TPU — so each step broadcasts a lane-dense (1, bf) row of
frontier distances over its (bk, bf) weight block.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl


def _frontier_cand_kernel(df_ref, w_ref, out_ref):
    """df_ref: (1, bf) frontier distances; w_ref/out_ref: (bk, bf)
    out-ELL weight / candidate blocks."""
    out_ref[...] = df_ref[...] + w_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_f", "block_k", "interpret")
)
def frontier_cand(
    df: jax.Array,
    w: jax.Array,
    *,
    block_f: int = 256,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """df[f] + w[k, f] for the compacted frontier: df (F,), w (K, F) ->
    (K, F).  Requires F % block_f == 0 and K % block_k == 0 (ops.py pads
    to the grid)."""
    K, F = w.shape
    if block_k is None:
        block_k = K
    assert df.shape == (F,), (df.shape, F)
    assert F % block_f == 0 and K % block_k == 0, (F, K, block_f, block_k)
    blk = pl.BlockSpec((block_k, block_f), lambda f, k: (k, f))
    return pl.pallas_call(
        _frontier_cand_kernel,
        grid=(F // block_f, K // block_k),
        in_specs=[pl.BlockSpec((1, block_f), lambda f, k: (0, f)), blk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((K, F), w.dtype),
        interpret=interpret,
    )(df[None, :], w)
