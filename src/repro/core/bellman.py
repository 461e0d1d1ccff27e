"""Relax-to-fixpoint SSSP — the paper's Algorithm 3/4 (CUDA analogue).

The paper's CUDA kernel assigns one thread per vertex ``tid``; each thread
sweeps tid's outgoing edges doing ``atomicMin(&dist[v], dist[tid]+w)`` and
marks ``updated[v]``; the host loops the kernel until a Thrust
``reduce(logical_or)`` over ``updated`` reports no change.

TPU adaptation (DESIGN.md §2): TPU has no atomics and no free-running scalar
threads. One full kernel launch computes, for every v,

    new_dist[v] = min(dist[v], min_u (dist[u] + A[u, v]))

which is exactly a **min-plus matrix-vector product** — an associative
reduction the TPU executes deterministically, replacing atomicMin.  The
fixpoint (and hence the result) is identical to the CUDA version; iteration
count is bounded by the shortest-path hop diameter, the same bound behind the
paper's ``repeat ... until not anyUpdated``.

Device-side convergence: ``lax.while_loop`` on ``jnp.any(new != old)`` — the
check never leaves the device, which is precisely why the paper reached for
Thrust instead of copying ``updated[]`` back to the host.

Also here (beyond-paper, DESIGN.md §2):
  * ``sssp_bellman_sharded`` — the fixpoint engine distributed over a mesh
    axis: ONE all-gather of the dist vector per sweep instead of the
    Dijkstra engine's one MINLOC allreduce per *vertex*.  This directly
    attacks the paper's own diagnosis of its MPI scaling collapse (§V.2).
  * ``use_frontier`` — rows whose dist did not improve last sweep are masked
    to INF so they contribute nothing; keeps the dense layout (no gathers).
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


def relax_sweep_ref(dist: jax.Array, adj: jax.Array) -> jax.Array:
    """One full relaxation sweep: min-plus matvec.  (n,),(n,n) -> (n,)."""
    return jnp.minimum(dist, jnp.min(dist[:, None] + adj, axis=0))


def _sweep_blocked(dist, adj, block: int):
    """Sweep with the contraction blocked over u — same math, smaller
    live intermediate ((block, n) instead of (n, n)); XLA fuses the rest."""
    n = adj.shape[0]
    if n % block != 0 or n == block:
        return relax_sweep_ref(dist, adj)

    def body(carry, ub):
        du = lax.dynamic_slice_in_dim(dist, ub * block, block)
        au = lax.dynamic_slice_in_dim(adj, ub * block, block, axis=0)
        cand = jnp.min(du[:, None] + au, axis=0)
        return jnp.minimum(carry, cand), None

    out, _ = lax.scan(body, dist, jnp.arange(n // block))
    return out


@functools.partial(
    jax.jit, static_argnames=("sweep_fn", "max_sweeps", "use_frontier")
)
def sssp_bellman(
    adj: jax.Array,
    source: jax.Array,
    *,
    sweep_fn: Optional[Callable] = None,
    max_sweeps: int | None = None,
    use_frontier: bool = False,
):
    """Fixpoint SSSP (paper Alg. 3).  Returns (dist, pred, num_sweeps).

    sweep_fn(dist, adj) -> new_dist lets callers swap in the Pallas kernel
    (kernels/sssp_relax/ops.py) for the jnp path; both satisfy the same
    oracle (kernels/sssp_relax/ref.py).
    """
    n = adj.shape[0]
    cap = n if max_sweeps is None else max_sweeps
    sweep = sweep_fn or relax_sweep_ref
    dist0 = jnp.full((n,), INF, adj.dtype).at[source].set(0.0)

    def cond(carry):
        dist, prev, it, frontier = carry
        return (it < cap) & jnp.any(dist != prev)

    def body(carry):
        dist, _, it, frontier = carry
        src = jnp.where(frontier, dist, INF) if use_frontier else dist
        new = sweep(src, adj)
        new = jnp.minimum(new, dist)  # monotone even under frontier masking
        return new, dist, it + 1, (new < dist) if use_frontier else frontier
    frontier0 = dist0 < INF
    # prev sentinel differs from dist0 so the loop runs at least once.
    prev0 = jnp.full_like(dist0, -1.0)
    dist, _, sweeps, _ = lax.while_loop(
        cond, body, (dist0, prev0, jnp.int32(0), frontier0)
    )
    pred = predecessors_from_dist(dist, adj, source)
    return dist, pred, sweeps


def predecessors_from_dist(dist, adj, source):
    """Recover pred[] at the fixpoint: pred[v] = argmin_u dist[u] + A[u,v].

    At the fixpoint dist[v] == min_u(dist[u] + A[u,v]) for every reachable
    v != source, so this reproduces a valid shortest-path tree (the paper
    updates pred inside the kernel; doing it once at the end is equivalent
    at the fixpoint and cheaper — recorded in EXPERIMENTS.md §Perf).

    The diagonal (A[v,v] == 0, i.e. via[v,v] == dist[v]) is masked out:
    it always ties the fixpoint minimum, and letting the argmin pick it
    would emit pred[v] == v — a self-loop that breaks path reconstruction.

    The result is a valid tree whenever edge weights are strictly positive
    (then every pred edge strictly decreases dist, so no cycles).  Known
    limitation shared with the CSR recovery: explicit zero-weight edges
    between equal-dist vertices can make two such vertices pick each other
    (a 2-cycle); orienting zero-weight components needs a multi-pass
    recovery no single argmin tie-break can express.
    """
    n = adj.shape[0]
    via = dist[:, None] + adj                     # (u, v)
    diag = jnp.arange(n)
    via = via.at[diag, diag].set(INF)             # no self-predecessors
    u_best = jnp.argmin(via, axis=0).astype(jnp.int32)
    reached = jnp.isfinite(dist)
    pred = jnp.where(reached, u_best, -1)
    return pred.at[source].set(-1)


def sssp_bellman_sharded(
    adj_padded: jax.Array,
    source: jax.Array,
    mesh: jax.sharding.Mesh,
    *,
    axis: str = "data",
    max_sweeps: int | None = None,
):
    """Distributed fixpoint SSSP: columns sharded, dist replicated.

    Per sweep each device relaxes its own column block (a (n, loc_n)
    min-plus matvec) and the new dist vector is reassembled with ONE
    ``lax.all_gather`` — one collective per sweep (≈ hop diameter sweeps)
    vs. Dijkstra's one MINLOC per vertex (n collectives).  This is the
    "better-granularity synchronization" the paper calls for in §V.2.

    Returns (dist (n_pad,), pred (n_pad,), sweeps).
    """
    nprocs = axis_size(mesh, axis)
    n_pad = adj_padded.shape[0]
    assert n_pad % nprocs == 0
    loc_n = n_pad // nprocs
    cap = int(max_sweeps if max_sweeps is not None else n_pad)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, axis), P()),
        out_specs=(P(axis), P(axis), P()),
    )
    def run(adj_loc, src):
        my_p = lax.axis_index(axis)
        v_base = my_p * loc_n
        dist0 = jnp.full((n_pad,), INF, adj_loc.dtype).at[src].set(0.0)
        # initial carries are device-invariant; body outputs are varying.
        dist0 = varying(dist0, axis)
        prev0 = varying(jnp.full((n_pad,), -1.0, adj_loc.dtype), axis)

        def cond(c):
            dist, prev, it = c
            return (it < cap) & jnp.any(dist != prev)

        def body(c):
            dist, _, it = c
            loc_new = jnp.min(dist[:, None] + adj_loc, axis=0)   # (loc_n,)
            mine = lax.dynamic_slice_in_dim(dist, v_base, loc_n)
            loc_new = jnp.minimum(mine, loc_new)
            new = lax.all_gather(loc_new, axis, tiled=True)      # (n_pad,)
            return new, dist, it + 1

        it0 = varying(jnp.int32(0), axis)
        dist, _, sweeps = lax.while_loop(cond, body, (dist0, prev0, it0))
        # local pred for owned vertices, from the fixpoint dist.  Mask the
        # diagonal (global row v for local column v) so the argmin never
        # emits a pred[v] == v self-loop (same as predecessors_from_dist).
        via = dist[:, None] + adj_loc                            # (n, loc_n)
        loc_cols = jnp.arange(loc_n, dtype=jnp.int32)
        via = via.at[v_base + loc_cols, loc_cols].set(INF)
        u_best = jnp.argmin(via, axis=0).astype(jnp.int32)
        mine = lax.dynamic_slice_in_dim(dist, v_base, loc_n)
        owned = v_base + jnp.arange(loc_n, dtype=jnp.int32)
        pred = jnp.where(jnp.isfinite(mine) & (owned != src), u_best, -1)
        # sweeps is identical on every device; psum-and-divide makes it
        # provably axis-invariant so it can leave with out_specs P().
        sweeps_inv = lax.psum(sweeps, axis) // nprocs
        return mine, pred, sweeps_inv

    dist, pred, sweeps = run(adj_padded, jnp.asarray(source, jnp.int32))
    return dist, pred, sweeps
