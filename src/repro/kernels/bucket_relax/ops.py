"""jit'd public wrappers for the bucket_relax Pallas kernel.

``bucket_relax_block`` pads the light in-ELL operands to the kernel grid —
INF for the distance and weight slots, id 0 for index slots, none of which
can improve a label or raise a flag — gathers the candidate source
distances, then dispatches and OR-reduces the per-block improvement flags.

``make_bucket_pull_fn`` adapts it to core/delta_stepping.py's pull
contract ``pull(dist, ops, hi) -> (new_dist, go)``; the result is
bitwise-equal to the flat ``make_light_pull_fn`` (same candidate multiset
plus INF no-ops from padding, and elementwise-exact flag comparisons), so
``delta_stepping_kernel`` solves match ``delta_stepping`` bit for bit.

Off the TPU ``interpret=True`` executes the kernel body in Python; on TPU
the same call lowers to Mosaic.  ``auto_interpret()`` picks
per-backend so library code stays platform-agnostic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.obs.metrics import mark_trace
from repro.kernels.bucket_relax import kernel as K
from repro.kernels.common import aligned as _aligned
from repro.kernels.common import auto_interpret
from repro.kernels.common import pad_to as _pad_to

INF = jnp.inf


@functools.partial(
    jax.jit, static_argnames=("block_v", "block_k", "interpret")
)
def bucket_relax_block(
    dist: jax.Array,
    ell_idx: jax.Array,
    ell_w: jax.Array,
    hi: jax.Array,
    *,
    block_v: int = 256,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Kernel-backed fused light pull: matches ref.bucket_relax_ref
    bitwise.  dist (n,), ell_idx/ell_w (n, K), hi scalar ->
    (new_dist (n,), go bool).  Pads n up to the v-block (INF rows) and K
    up to the k-block ((0, INF) slots) internally.
    """
    if interpret is None:
        interpret = auto_interpret()
    n = dist.shape[0]
    Kw = ell_w.shape[1]
    K8 = _aligned(max(Kw, 1), 8)
    if block_k is not None:
        bk = block_k
    elif K8 <= 128:
        bk = K8
    else:
        # largest 8-multiple divisor <= 128, as in csr_relax/ops.py: keeps
        # K_pad == K8 instead of force-padding to a 128 multiple.
        bk = next((d for d in range(128, 7, -8) if K8 % d == 0), 128)
    V_pad = _aligned(max(n, 1), block_v)
    K_pad = _aligned(K8, bk)
    d = _pad_to(dist, V_pad, 0, INF)
    idx = _pad_to(_pad_to(ell_idx, V_pad, 0, 0), K_pad, 1, 0)
    w = _pad_to(_pad_to(ell_w, V_pad, 0, INF), K_pad, 1, INF)
    # the row gather stays in XLA (Mosaic lowers only 2-D gathers); the
    # kernel reads the candidates slot-major, see kernel.py.
    new, flags = K.bucket_relax(
        dist[idx.T], w.T, d, hi, block_v=block_v, block_k=bk,
        interpret=interpret
    )
    return new[:n], jnp.any(flags > 0)


@functools.lru_cache(maxsize=None)
def make_bucket_pull_fn(*, block_v: int = 256, block_k: int | None = None,
                        interpret: bool | None = None):
    """Adapter producing the kernel-backed light pull for
    core.delta_stepping.sssp_delta_stepping — consumes the operands'
    light in-ELL view.

    Memoized so repeated calls return the *same* closure: ``pull_fn`` is a
    static jit argument of the engine, and a fresh closure per call would
    retrace + recompile the whole phase loop every solve.
    """

    def pull(dist, ops, hi):
        mark_trace("bucket_kernel_pull")
        return bucket_relax_block(
            dist, ops["light_ell_idx"], ops["light_ell_w"], hi,
            block_v=block_v, block_k=block_k, interpret=interpret,
        )

    return pull
