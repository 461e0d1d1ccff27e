"""Relax-to-fixpoint SSSP over sparse CSR edges — O(m) per sweep.

Same fixpoint iteration as core/bellman.py (the paper's Algorithm 3/4), but
the relax sweep is a **segment-min over the edge list** instead of a dense
min-plus matvec:

    via[e]  = dist[src[e]] + w[e]                 (one add per edge)
    cand[v] = segment_min(via, dst)               (associative min per vertex)
    new[v]  = min(dist[v], cand[v])

This touches each of the m stored arcs exactly once per sweep — O(m) work —
where the dense sweep reads the full n² matrix however sparse the graph is.
That is precisely the paper's §V complaint about its adjacency-matrix data
structure, and the reason Table II's 40k-vertex/120k-edge graph is the dense
formulation's ceiling.  The segment-min is the TPU-legal stand-in for the
CUDA kernel's ``atomicMin`` over incoming edges: an associative reduction
with deterministic result, the same argument as bellman.py's matvec.

The kernel path (api engine ``bellman_csr_kernel``) swaps ``sweep_fn`` for
the Pallas padded-ELL kernel in kernels/csr_relax — fixed-width rows so the
block shapes are static, mirroring the paper's padding trick.

Frontier-restricted relaxation lives in core/frontier.py (api engines
``frontier`` / ``frontier_kernel``): it compacts the improved vertices and
touches only their out-edges, O(frontier out-degree) per sweep instead of
this engine's O(m).  ``sssp_multisource_csr`` below is the batched twin:
S sources share one (S, m) gather of the edge arrays per sweep — the
sparse analogue of core/multisource.py's min-plus matmul.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.multisource import init_dist
from repro.obs.metrics import mark_trace

INF = jnp.inf


def csr_operands(cg, *, with_ell: bool = False) -> dict:
    """Stage a core.csr.CsrGraph's arrays onto the device as the pytree the
    engine threads through jit.  ``with_ell`` adds the padded-ELL view the
    Pallas kernel consumes (skipped for the pure segment-min path).

    Deliberately NOT memoized on the CsrGraph (unlike its host-side
    views): caching jax buffers on a long-lived host container would pin
    device memory for the graph's lifetime, and the host numpy views are
    already cached so repeat staging is a plain O(n + m) copy.
    """
    ops = {
        "src": jnp.asarray(cg.indices),
        "dst": jnp.asarray(cg.dst_ids()),
        "w": jnp.asarray(cg.weights),
    }
    if with_ell:
        ell_idx, ell_w = cg.ell()
        ops["ell_idx"] = jnp.asarray(ell_idx)
        ops["ell_w"] = jnp.asarray(ell_w)
    return ops


def segment_relax_sweep(dist: jax.Array, csr: dict) -> jax.Array:
    """One O(m) relax sweep: per-vertex min over incoming-edge candidates,
    folded with the self-distance — matches kernels/csr_relax/ref.py's
    ``segment_relax_ref`` and the sweep-fn contract of every other engine
    sweep (the fold also erases the segment identity on vertices with no
    incoming arcs)."""
    via = dist[csr["src"]] + csr["w"]
    cand = jax.ops.segment_min(
        via, csr["dst"], num_segments=dist.shape[0], indices_are_sorted=True
    )
    return jnp.minimum(dist, cand)


@functools.partial(
    jax.jit, static_argnames=("n", "sweep_fn", "max_sweeps")
)
def sssp_bellman_csr(
    csr: dict,
    source: jax.Array,
    *,
    n: int,
    sweep_fn: Optional[Callable] = None,
    max_sweeps: int | None = None,
):
    """Fixpoint SSSP on CSR operands.  Returns
    ``(dist, pred, num_sweeps, converged)``.

    csr: the pytree from :func:`csr_operands`.  ``sweep_fn(dist, csr) ->
    new_dist`` (self-distance folded in, like bellman.py's sweep_fn) lets
    callers swap in the Pallas ELL kernel
    (kernels/csr_relax/ops.make_csr_sweep_fn) for the segment-min path;
    both satisfy the same oracle (kernels/csr_relax/ref.py).

    ``converged`` is the solver guardrail (serve/errors.py's
    ``NotConverged`` consumes it): True iff the loop exited because the
    last sweep changed nothing — under a tight ``max_sweeps=`` cap the
    flag goes False instead of silently returning labels above their
    fixpoint.  The hop-diameter default cap (n) always converges on
    nonnegative weights, so the flag is only ever False when a caller
    caps the loop (or, later, when Johnson's reweighting meets a
    negative cycle).

    Every sweep relaxes all m stored arcs; for frontier-restricted O(active
    out-degree) sweeps use core.frontier.sssp_frontier instead (the old
    dead-defaulted ``use_frontier`` flag here was removed in its favor).
    """
    # Python body => trace time only; counts (re)traces, free when cached
    mark_trace("bellman_csr")
    cap = n if max_sweeps is None else max_sweeps
    sweep = sweep_fn or segment_relax_sweep
    dist0 = jnp.full((n,), INF, csr["w"].dtype).at[source].set(0.0)

    def cond(carry):
        dist, prev, it = carry
        return (it < cap) & jnp.any(dist != prev)

    def body(carry):
        dist, _, it = carry
        new = jnp.minimum(sweep(dist, csr), dist)
        return new, dist, it + 1

    # prev sentinel differs from dist0 so the loop runs at least once.
    prev0 = jnp.full_like(dist0, -1.0)
    dist, prev, sweeps = lax.while_loop(
        cond, body, (dist0, prev0, jnp.int32(0))
    )
    converged = ~jnp.any(dist != prev)
    pred = predecessors_from_dist_csr(dist, csr, source)
    return dist, pred, sweeps, converged


def segment_relax_sweep_multi(D: jax.Array, csr: dict) -> jax.Array:
    """Batched O(S·m) relax sweep over a (S, n) distance matrix: the sparse
    twin of multisource.relax_sweep_multi_ref.  One gather of the edge
    index arrays serves all S sources (vmap hoists the shared ``src``/
    ``dst`` loads), so arithmetic intensity rises S× exactly as in the
    dense batched engine — per-row results are bitwise identical to S
    independent ``segment_relax_sweep`` calls by construction."""
    return jax.vmap(lambda d: segment_relax_sweep(d, csr))(D)


@functools.partial(jax.jit, static_argnames=("n", "sweep_fn", "max_sweeps"))
def sssp_multisource_csr(
    csr: dict,
    sources: jax.Array,
    *,
    n: int,
    sweep_fn: Optional[Callable] = None,
    max_sweeps: int | None = None,
):
    """Batched fixpoint SSSP from S sources on CSR operands.  Returns
    ``(D (S, n), sweeps, converged)``; per-source rows equal S
    single-source solves run to their joint fixpoint (the sweep count is
    the max over sources).  ``converged`` is the joint flag — False means
    at least one row may sit above its fixpoint (same guardrail contract
    as :func:`sssp_bellman_csr`).  pred is recovered on demand —
    api.recover_pred reuses the O(m) recovery per row.  The sweep and the
    convergence test carry the ``jax.named_scope`` names
    ``multisource_csr.relax`` and ``multisource_csr.test``."""
    mark_trace("multisource_csr")
    cap = n if max_sweeps is None else max_sweeps
    sweep = sweep_fn or segment_relax_sweep_multi
    D0 = init_dist(n, sources, csr["w"].dtype)

    def cond(carry):
        D, prev, it = carry
        with jax.named_scope("multisource_csr.test"):
            return (it < cap) & jnp.any(D != prev)

    def body(carry):
        D, _, it = carry
        with jax.named_scope("multisource_csr.relax"):
            new = jnp.minimum(sweep(D, csr), D)
        return new, D, it + 1

    prev0 = jnp.full_like(D0, -1.0)
    D, prev, sweeps = lax.while_loop(cond, body, (D0, prev0, jnp.int32(0)))
    with jax.named_scope("multisource_csr.test"):
        converged = ~jnp.any(D != prev)
    return D, sweeps, converged


def predecessors_from_dist_csr(dist: jax.Array, csr: dict, source) -> jax.Array:
    """Recover pred[] at the fixpoint from the edge list.

    At the fixpoint every reachable v != source has an incoming arc (u, w)
    with dist[v] == dist[u] + w; among those we take the lowest u — the same
    deterministic tie-break as the dense argmin (bellman.py), at O(m) cost
    instead of materializing the (n, n) ``via`` matrix.

    Valid tree whenever weights are strictly positive (pred edges strictly
    decrease dist).  Same known limitation as the dense recovery: explicit
    zero-weight edges between equal-dist vertices can form pred 2-cycles.
    """
    n = dist.shape[0]
    via = dist[csr["src"]] + csr["w"]
    best = jax.ops.segment_min(
        via, csr["dst"], num_segments=n, indices_are_sorted=True
    )
    attains = via <= best[csr["dst"]]
    u_cand = jnp.where(attains, csr["src"].astype(jnp.int32), jnp.int32(n))
    u_best = jax.ops.segment_min(
        u_cand, csr["dst"], num_segments=n, indices_are_sorted=True
    )
    reached = jnp.isfinite(dist) & (u_best < n)
    pred = jnp.where(reached, u_best, -1)
    return pred.at[source].set(-1)
