"""Column-parallel Dijkstra — the paper's Algorithm 2 (MPI analogue).

The paper 1-D-partitions the adjacency matrix by *columns* across P
processes (each process owns n/P vertices), pads n to a multiple of P, and
per iteration does: local argmin over the unvisited owned vertices, a global
``MPI_Allreduce(MINLOC)``, then a local relax of the owned column block from
the winning vertex's row; results are reassembled with ``MPI_Gather``.

TPU/JAX mapping (see DESIGN.md §2):
  * processes            -> mesh devices along one axis, via jax.shard_map
  * column partition     -> in_specs P(None, axis) on the padded adjacency
  * MPI_Allreduce MINLOC -> minloc_allgather (baseline: one lax.all_gather of
                            P (dist, index) candidates + deterministic argmin)
                            or minloc_pmin (two lax.pmin, hillclimb variant)
  * MPI_Gather           -> out_specs P(axis): GSPMD reassembles shards
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.core._axes import axis_size, varying

INF = jnp.inf

MinlocImpl = Literal["allgather", "pmin", "packed"]


def minloc_allgather(d: jax.Array, idx: jax.Array, axis: str):
    """MINLOC via one all-gather of P candidate pairs (baseline, 1 collective).

    Deterministic tie-break: smallest global index among equal distances —
    matching the serial argmin semantics exactly.
    """
    ds = lax.all_gather(d, axis)          # (P,)
    idxs = lax.all_gather(idx, axis)      # (P,)
    best = jnp.min(ds)
    cand = jnp.where(ds == best, idxs, jnp.iinfo(jnp.int32).max)
    return best, jnp.min(cand)


def minloc_pmin(d: jax.Array, idx: jax.Array, axis: str):
    """MINLOC via two min-allreduces (latency 2·alpha, O(1) payload).

    First pmin finds the winning distance; the second pmin selects the
    smallest index whose local candidate equals it.
    """
    best = lax.pmin(d, axis)
    cand = jnp.where(d == best, idx, jnp.iinfo(jnp.int32).max)
    return best, lax.pmin(cand, axis)


def minloc_packed(d: jax.Array, idx: jax.Array, axis: str):
    """MINLOC in ONE collective (§Perf hillclimb B).

    Distances are non-negative f32, so their IEEE-754 bit patterns are
    order-preserving as u32 (+inf included).  Packing [dist_bits, idx]
    into one (2,)-u32 payload and doing a single all-gather halves the
    per-iteration collective *count* — and the Dijkstra engine is
    latency-bound (n iterations × α), so this directly attacks the
    dominant roofline term.  Tie-break (smallest index at equal distance)
    matches the serial argmin exactly.
    """
    d_bits = jax.lax.bitcast_convert_type(d, jnp.uint32)
    packed = jnp.stack([d_bits, idx.astype(jnp.uint32)])        # (2,)
    allp = lax.all_gather(packed, axis)                         # (P, 2)
    bits, idxs = allp[:, 0], allp[:, 1]
    best_bits = jnp.min(bits)
    cand = jnp.where(bits == best_bits, idxs, jnp.uint32(0xFFFFFFFF))
    best_idx = jnp.min(cand).astype(jnp.int32)
    best = jax.lax.bitcast_convert_type(best_bits, jnp.float32)
    return best, best_idx


_MINLOC = {"allgather": minloc_allgather, "pmin": minloc_pmin,
           "packed": minloc_packed}


def dijkstra_sharded(
    adj_padded: jax.Array,
    source: jax.Array,
    mesh: jax.sharding.Mesh,
    *,
    axis: str = "data",
    n_true: int | None = None,
    minloc: MinlocImpl = "allgather",
):
    """Parallel Dijkstra over ``mesh[axis]`` (paper Alg. 2).

    adj_padded: (n_pad, n_pad) with n_pad a multiple of mesh.shape[axis]
                (use Graph.padded(P) — the paper's padding step).
    n_true:     true vertex count; iterations run n_true times as in the
                paper's ``for i in 0..n-1`` (padding vertices are INF-
                isolated and can never win the argmin).
    Returns (dist, pred) of shape (n_pad,): valid entries are [:n_true].
    """
    nprocs = axis_size(mesh, axis)
    n_pad = adj_padded.shape[0]
    assert n_pad % nprocs == 0, "pad the graph first (Graph.padded)"
    loc_n = n_pad // nprocs
    iters = int(n_true if n_true is not None else n_pad)
    minloc_fn = _MINLOC[minloc]

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, axis), P()),
        out_specs=(P(axis), P(axis)),
    )
    def run(adj_loc, src):
        # adj_loc: (n_pad, loc_n) — this device's column block.
        my_p = lax.axis_index(axis)
        v_base = my_p * loc_n                       # first owned global vertex
        owned = v_base + jnp.arange(loc_n, dtype=jnp.int32)

        loc_dist = jnp.where(owned == src, 0.0, INF).astype(adj_loc.dtype)
        # varying: mark the device-invariant initial carries as axis-varying so
        # the fori_loop carry types match the (varying) body outputs.
        loc_pred = varying(jnp.full((loc_n,), -1, jnp.int32), axis)
        loc_visited = varying(jnp.zeros((loc_n,), jnp.bool_), axis)

        def body(_, carry):
            loc_dist, loc_pred, loc_visited = carry
            # --- local argmin over unvisited owned vertices ---------------
            masked = jnp.where(loc_visited, INF, loc_dist)
            loc_arg = jnp.argmin(masked)
            loc_min = masked[loc_arg]
            loc_u = (v_base + loc_arg).astype(jnp.int32)
            # unreachable local candidate must not win ties at INF with a
            # lower index; push its index to +inf sentinel.
            loc_u = jnp.where(jnp.isfinite(loc_min), loc_u,
                              jnp.iinfo(jnp.int32).max)
            # --- global MINLOC (the paper's MPI_Allreduce) -----------------
            du, u = minloc_fn(loc_min, loc_u, axis)
            u_safe = jnp.clip(u, 0, n_pad - 1)
            # --- owner marks u visited -------------------------------------
            off = jnp.clip(u_safe - v_base, 0, loc_n - 1)
            is_mine = (u_safe >= v_base) & (u_safe < v_base + loc_n)
            is_mine &= jnp.isfinite(du)
            loc_visited = loc_visited.at[off].set(loc_visited[off] | is_mine)
            # --- relax owned columns from row u ----------------------------
            row_u = lax.dynamic_slice_in_dim(adj_loc, u_safe, 1, axis=0)[0]
            cand = du + row_u
            better = (cand < loc_dist) & ~loc_visited
            loc_dist = jnp.where(better, cand, loc_dist)
            loc_pred = jnp.where(better, u, loc_pred)
            return loc_dist, loc_pred, loc_visited

        loc_dist, loc_pred, _ = lax.fori_loop(
            0, iters, body, (loc_dist, loc_pred, loc_visited)
        )
        return loc_dist, loc_pred

    return run(adj_padded, jnp.asarray(source, jnp.int32))
