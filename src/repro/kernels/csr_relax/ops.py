"""jit'd public wrappers for the csr_relax Pallas kernel.

Pad the (n, K) ELL arrays to the block grid — INF-weight slots pointing at
vertex 0 can never win a min, the same unreachable-padding argument as the
paper's padded matrix (§III-B.2) — gather the candidate source distances in
XLA, then dispatch and fold the self-distance ``min(dist, ·)`` back in.

Off the TPU ``interpret=True`` executes the kernel body in Python; on TPU
the same call lowers to Mosaic.  ``auto_interpret()`` picks
per-backend so library code stays platform-agnostic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.obs.metrics import mark_trace
from repro.kernels.common import aligned as _aligned
from repro.kernels.common import auto_interpret
from repro.kernels.common import pad_to as _pad_to
from repro.kernels.csr_relax import kernel as K

INF = jnp.inf


@functools.partial(
    jax.jit, static_argnames=("block_v", "block_k", "interpret")
)
def csr_relax_sweep(
    dist: jax.Array,
    ell_idx: jax.Array,
    ell_w: jax.Array,
    *,
    block_v: int = 256,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """One sparse relaxation sweep via the Pallas ELL kernel: matches
    ref.ell_relax_ref bitwise.

    dist (n,), ell_idx/ell_w (n, K) -> (n,).  Pads n up to the v-block and
    K up to the k-block internally; padding rows/slots are unreachable.
    """
    if interpret is None:
        interpret = auto_interpret()
    n = dist.shape[0]
    Kw = ell_idx.shape[1]
    K8 = _aligned(max(Kw, 1), 8)
    if block_k is not None:
        bk = block_k
    elif K8 <= 128:
        bk = K8
    else:
        # largest 8-multiple divisor of K8 that fits a VREG-friendly step —
        # keeps K_pad == K8 (no force-padding to a 128 multiple, which
        # could nearly double the per-sweep work for K just above 128).
        bk = next((d for d in range(128, 7, -8) if K8 % d == 0), 128)
    n_pad = _aligned(n, block_v)
    K_pad = _aligned(K8, bk)
    idx = _pad_to(_pad_to(ell_idx, n_pad, 0, 0), K_pad, 1, 0)
    w = _pad_to(_pad_to(ell_w, n_pad, 0, INF), K_pad, 1, INF)
    # the row gather stays in XLA (Mosaic lowers only 2-D gathers); the
    # kernel reads the candidates slot-major, see kernel.py.
    out = K.ell_relax(
        dist[idx.T], w.T, block_v=block_v, block_k=bk, interpret=interpret
    )
    return jnp.minimum(dist, out[:n])


@functools.lru_cache(maxsize=None)
def make_csr_sweep_fn(*, block_v: int = 256, block_k: int | None = None,
                      interpret: bool | None = None):
    """Adapter producing ``sweep_fn(dist, csr_operands)`` for
    core.bellman_csr.sssp_bellman_csr — consumes the pytree's ELL view.

    Memoized so repeated calls return the *same* closure: ``sweep_fn`` is a
    static jit argument of the engine, and a fresh closure per call would
    retrace + recompile the whole fixpoint loop every solve.
    """
    def fn(dist, csr):
        mark_trace("csr_kernel_sweep")
        return csr_relax_sweep(
            dist, csr["ell_idx"], csr["ell_w"],
            block_v=block_v, block_k=block_k, interpret=interpret,
        )
    return fn
