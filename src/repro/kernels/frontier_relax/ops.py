"""jit'd public wrappers for the frontier_relax Pallas kernel.

``frontier_cand_block`` pads the compacted-frontier operands to the kernel
grid — sentinel ids (n) for frontier slots, INF for weight slots, both of
which produce INF candidates the scatter-min ignores — gathers the frontier
distances, then dispatches.

``make_frontier_sweep_fn`` assembles a full frontier sweep satisfying
core/frontier.py's sweep contract: an inner ``lax.while_loop`` walks the
compacted frontier ``block_f`` rows at a time (trip count tracks the actual
frontier size), gathers each chunk's padded out-ELL windows, generates
candidates with the kernel, and scatter-mins them in XLA.  Bitwise-equal to
the flat-CSR default sweep: same candidate multiset plus INF no-ops.

Off the TPU ``interpret=True`` executes the kernel body in Python; on TPU
the same call lowers to Mosaic.  ``auto_interpret()`` picks
per-backend so library code stays platform-agnostic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.obs.metrics import mark_trace
from repro.kernels.common import aligned as _aligned
from repro.kernels.common import auto_interpret
from repro.kernels.common import pad_to as _pad_to
from repro.kernels.frontier_relax import kernel as K

INF = jnp.inf


@functools.partial(
    jax.jit, static_argnames=("block_f", "block_k", "interpret")
)
def frontier_cand_block(
    dist: jax.Array,
    fids: jax.Array,
    ell_w: jax.Array,
    *,
    block_f: int = 256,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Kernel-backed candidate generation for a compacted frontier chunk:
    matches ref.frontier_cand_ref bitwise.

    dist (n,), fids (F,), ell_w (F, K) -> (F, K).  Pads F up to the f-block
    (sentinel id n) and K up to the k-block (INF) internally.
    """
    if interpret is None:
        interpret = auto_interpret()
    n = dist.shape[0]
    F, Kw = ell_w.shape
    K8 = _aligned(max(Kw, 1), 8)
    if block_k is not None:
        bk = block_k
    elif K8 <= 128:
        bk = K8
    else:
        # largest 8-multiple divisor <= 128, as in csr_relax/ops.py: keeps
        # K_pad == K8 instead of force-padding to a 128 multiple.
        bk = next((d for d in range(128, 7, -8) if K8 % d == 0), 128)
    F_pad = _aligned(max(F, 1), block_f)
    K_pad = _aligned(K8, bk)
    f = _pad_to(fids, F_pad, 0, n)                   # sentinel -> INF cand
    w = _pad_to(_pad_to(ell_w, F_pad, 0, INF), K_pad, 1, INF)
    # the frontier-distance gather stays in XLA (Mosaic lowers only 2-D
    # gathers); the kernel reads slot-major blocks, see kernel.py.
    df = jnp.where(f < n, dist[jnp.minimum(f, n - 1)], INF)
    out = K.frontier_cand(
        df, w.T, block_f=block_f, block_k=bk, interpret=interpret
    ).T
    return out[:F, :Kw]


@functools.lru_cache(maxsize=None)
def make_frontier_sweep_fn(*, block_f: int = 256, block_k: int | None = None,
                           interpret: bool | None = None):
    """Adapter producing the kernel-backed frontier sweep for
    core.frontier.sssp_frontier — consumes the operands' out-ELL view.

    Memoized so repeated calls return the *same* closure: ``sweep_fn`` is a
    static jit argument of the engine, and a fresh closure per call would
    retrace + recompile the whole fixpoint loop every solve.
    """

    def sweep(dist, fids, starts, off, E, fcount, ops):
        mark_trace("frontier_kernel_sweep")
        n = dist.shape[0]
        n_pad = _aligned(n, block_f)
        fpad = _pad_to(fids, n_pad, 0, jnp.int32(n))

        def cond(carry):
            _, c = carry
            return c * block_f < fcount

        def body(carry):
            nd, c = carry
            blk = lax.dynamic_slice(fpad, (c * block_f,), (block_f,))
            rows = jnp.minimum(blk, n - 1)           # sentinel -> any row;
            tgt = ops["out_ell_idx"][rows]           # its candidates are INF
            ew = ops["out_ell_w"][rows]
            cand = frontier_cand_block(
                dist, blk, ew,
                block_f=block_f, block_k=block_k, interpret=interpret,
            )
            return nd.at[tgt].min(cand), c + 1

        nd, _ = lax.while_loop(cond, body, (dist, jnp.int32(0)))
        return nd

    return sweep
