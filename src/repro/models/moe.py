"""Mixture-of-Experts FFN with group-local sort-based capacity dispatch.

Design (see DESIGN.md §5): the classic GShard one-hot dispatch einsum costs
O(T·E·C·d) matmul FLOPs for what is really a gather, which would poison the
roofline's useful-FLOP ratio, and a *global* argsort over all tokens makes
the SPMD partitioner serialize routing through all-gathers.  Instead tokens
are split into G groups aligned with the data-parallel shards (GShard's
"groups", MaxText's dropping implementation): routing, stable argsort,
position-in-expert and capacity dropping are all computed *within* a group,
so under GSPMD every routing op stays shard-local:

    top-k ids -> per-group argsort -> position-in-expert
    -> (G, E, C, d) buffer scatter -> grouped expert einsums
    -> weighted scatter-add back, partial-summed over the expert axis.

All shapes are static; tokens past an expert's per-group capacity C are
dropped (scatter mode="drop"), matching capacity-factor semantics.  Expert
weights are (E, d, ff): EP shards the leading axis over "model" (kimi:
384/16) and the rules engine falls back to sharding ff when E is
indivisible (qwen2: 60).

Shared experts (DeepSeek/Qwen-MoE style) are a fused always-on SwiGLU of
width num_shared · moe_d_ff.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import get_abstract_mesh

from repro.models import common as cm
from repro.models.mlp import init_mlp, mlp
from repro.sharding.rules import constrain, dp_size


def _padded_experts(cfg) -> int:
    return max(cfg.num_experts, cfg.expert_pad_to)


def init_moe(key, cfg):
    E, d, ff = _padded_experts(cfg), cfg.d_model, cfg.moe_d_ff
    dt = cm.dtype_of(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "router": cm.dense_init(ks[0], (d, cfg.num_experts), jnp.float32),
        "wi_gate": cm.dense_init(ks[1], (E, d, ff), dt, fan_in=d),
        "wi_up": cm.dense_init(ks[2], (E, d, ff), dt, fan_in=d),
        "wo": cm.dense_init(ks[3], (E, ff, d), dt, fan_in=ff),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=cfg.num_shared_experts * ff)
    return p


def _capacity(Tg: int, cfg) -> int:
    c = int(cfg.capacity_factor * Tg * cfg.moe_top_k / max(cfg.num_experts, 1))
    return max(8, -(-c // 8) * 8)  # round up to 8


def _num_groups(T: int) -> int:
    """Dispatch groups = data-parallel shards (1 off-mesh), so per-group
    routing is local to a shard."""
    g = dp_size()
    while g > 1 and T % g:
        g //= 2
    return max(g, 1)


def moe(p, x, cfg):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Dispatches to the explicit expert-parallel shard_map implementation
    when cfg.moe_impl == "ep" and the ambient mesh has a "model" axis that
    divides the (padded) expert count; otherwise the GSPMD grouped path.
    """
    if cfg.moe_impl == "ep":
        am = _ambient_mesh()
        T_loc = (x.shape[0] * x.shape[1]) // max(dp_size(), 1)
        # decode-sized token counts (T_loc of a few) don't amortize the
        # per-layer combine psum — measured slower (EXPERIMENTS.md §Perf,
        # kimi decode_32k: 3.48s gspmd vs 5.15s ep); keep gspmd there.
        if (am is not None and "model" in am.axis_names
                and _padded_experts(cfg) % am.shape["model"] == 0
                and T_loc >= 1024):
            return moe_ep(p, x, cfg, am)
    return moe_gspmd(p, x, cfg)


def _ambient_mesh():
    return get_abstract_mesh()


def moe_gspmd(p, x, cfg):
    B, S, d = x.shape
    E, k = _padded_experts(cfg), cfg.moe_top_k
    T = B * S
    G = _num_groups(T)
    Tg = T // G
    C = _capacity(Tg, cfg)
    xt = constrain(x.reshape(G, Tg, d), "tokens_grouped")

    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)          # (G, Tg, E_real)
    if E > cfg.num_experts:                          # padded (dead) experts
        probs = jnp.pad(probs, ((0, 0), (0, 0), (0, E - cfg.num_experts)))
    w, ids = jax.lax.top_k(probs, k)                           # (G, Tg, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)                 # renormalize

    # ---- group-local sort-based dispatch -------------------------------
    flat_ids = ids.reshape(G, Tg * k)
    order = jnp.argsort(flat_ids, axis=-1, stable=True)        # (G, Tg*k)
    sorted_e = jnp.take_along_axis(flat_ids, order, axis=-1)
    counts = jax.vmap(lambda f: jnp.bincount(f, length=E))(flat_ids)
    starts = jnp.cumsum(counts, axis=-1) - counts              # (G, E)
    pos_in_e = (jnp.arange(Tg * k, dtype=jnp.int32)[None, :]
                - jnp.take_along_axis(starts, sorted_e, axis=-1))
    keep = pos_in_e < C
    slot = jnp.where(keep, sorted_e * C + pos_in_e, E * C)     # OOB -> drop
    token_of = order // k

    def scatter_group(xg, slot_g, tok_g):
        return jnp.zeros((E * C, d), x.dtype).at[slot_g].set(
            xg[tok_g], mode="drop")

    buf = jax.vmap(scatter_group)(xt, slot, token_of)          # (G, E*C, d)
    h = constrain(buf.reshape(G, E, C, d), "moe_buffer")

    # ---- expert FFN (grouped einsum over E) ----------------------------
    gte = jnp.einsum("gecd,edf->gecf", h, p["wi_gate"],
                     preferred_element_type=jnp.float32)
    u = jnp.einsum("gecd,edf->gecf", h, p["wi_up"],
                   preferred_element_type=jnp.float32)
    act = constrain((jax.nn.silu(gte) * u).astype(x.dtype), "moe_buffer")
    y = jnp.einsum("gecf,efd->gecd", act, p["wo"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    yflat = y.reshape(G, E * C, d)

    # ---- combine --------------------------------------------------------
    w_sorted = jnp.take_along_axis(w.reshape(G, Tg * k), order, axis=-1)

    def combine_group(yg, slot_g, tok_g, wg, keep_g):
        gathered = jnp.take(yg, jnp.minimum(slot_g, E * C - 1), axis=0)
        contrib = gathered * (wg * keep_g).astype(yg.dtype)[:, None]
        return jnp.zeros((Tg, d), yg.dtype).at[tok_g].add(contrib)

    out = jax.vmap(combine_group)(yflat, slot, token_of, w_sorted, keep)
    out = constrain(out, "tokens_grouped")

    # ---- aux load-balancing loss (Switch eq. 4, global) -----------------
    frac_tokens = jnp.sum(counts, axis=0).astype(jnp.float32) / (T * k)
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = (cfg.num_experts * jnp.sum(frac_tokens * mean_prob)
           * cfg.router_aux_weight)

    if "shared" in p:
        out = out + mlp(p["shared"], xt, cfg)
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# explicit expert-parallel implementation (shard_map over the "model" axis)
# ---------------------------------------------------------------------------
#
# Key structural fact: between TP layers the hidden states are *replicated*
# over the model axis (batch is sharded over dp only), so every model-rank
# already holds all of its dp-shard's tokens.  Expert-parallelism therefore
# needs NO dispatch all-to-all at all: each rank routes identically (same
# tokens, same router), keeps only the assignments that target its local
# expert slice, runs the expert FFN locally, scatter-adds its partial
# outputs, and one psum over the model axis completes the combine.
#
# Communication per layer: ONE all-reduce of (T_loc, d) — identical to the
# Megatron dense-MLP TP all-reduce — versus the GSPMD grouped path where
# the partitioner moves (G, E, C, d)-shaped buffers (~ k×capacity_factor
# times more bytes).  This is the §Perf hillclimb for the MoE cells.

import functools as _ft

from jax import lax as _lax
from jax.sharding import PartitionSpec as _P


def moe_ep(p, x, cfg, am):
    """x: (B, S, d) replicated over "model", batch over dp axes."""
    E, k = _padded_experts(cfg), cfg.moe_top_k
    ep_size = am.shape["model"]
    E_loc = E // ep_size
    B, S, d = x.shape
    dp_axes = tuple(a for a in ("pod", "data") if a in am.axis_names)
    x_spec = _P(dp_axes if B % max(dp_size(), 1) == 0 and dp_axes else None,
                None, None)

    @_ft.partial(
        _shard_map,
        in_specs=(x_spec, _P(), _P("model"), _P("model"), _P("model")),
        out_specs=(x_spec, _P()),
        check_vma=False,
    )
    def body(x_loc, router, wig, wiu, wog):
        Bl, Sl, _ = x_loc.shape
        T = Bl * Sl
        C = _capacity(T, cfg)
        xt = x_loc.reshape(T, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        if E > cfg.num_experts:
            probs = jnp.pad(probs, ((0, 0), (0, E - cfg.num_experts)))
        w, ids = jax.lax.top_k(probs, k)                     # (T, k)
        w = w / jnp.sum(w, axis=-1, keepdims=True)

        e_base = _lax.axis_index("model") * E_loc
        lids = jnp.where((ids >= e_base) & (ids < e_base + E_loc),
                         ids - e_base, E_loc)                # E_loc = drop
        flat = lids.reshape(T * k)
        order = jnp.argsort(flat, stable=True)
        sorted_e = flat[order]
        counts = jnp.bincount(flat, length=E_loc + 1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
        keep = (pos < C) & (sorted_e < E_loc)
        slot = jnp.where(keep, sorted_e * C + pos, E_loc * C)
        token_of = order // k

        buf = jnp.zeros((E_loc * C, d), x.dtype).at[slot].set(
            xt[token_of], mode="drop")
        h = buf.reshape(E_loc, C, d)
        g = jnp.einsum("ecd,edf->ecf", h, wig,
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("ecd,edf->ecf", h, wiu,
                       preferred_element_type=jnp.float32)
        act = (jax.nn.silu(g) * u).astype(x.dtype)
        y = jnp.einsum("ecf,efd->ecd", act, wog,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        yflat = y.reshape(E_loc * C, d)

        gathered = jnp.take(yflat, jnp.minimum(slot, E_loc * C - 1), axis=0)
        w_sorted = w.reshape(T * k)[order]
        contrib = gathered * (w_sorted * keep).astype(x.dtype)[:, None]
        partial = jnp.zeros((T, d), x.dtype).at[token_of].add(contrib)
        out = _lax.psum(partial, "model")                    # the combine

        # aux: aggregate routing stats globally (over dp shards) so the
        # load-balance signal matches the GSPMD path exactly; values are
        # already identical across model ranks (same tokens + router).
        cnt = jnp.bincount(ids.reshape(-1), length=E).astype(jnp.float32)
        psum_tok = jnp.sum(probs, axis=0)
        if dp_axes:
            cnt = _lax.psum(cnt, dp_axes)
            psum_tok = _lax.psum(psum_tok, dp_axes)
        T_global = T * max(dp_size(), 1)
        frac = cnt / (T_global * k)
        mean_prob = psum_tok / T_global
        aux = (cfg.num_experts * jnp.sum(frac * mean_prob)
               * cfg.router_aux_weight)
        aux = _lax.psum(aux, "model") / ep_size
        return out.reshape(Bl, Sl, d), aux

    out, aux = body(x, p["router"], p["wi_gate"], p["wi_up"], p["wo"])
    if "shared" in p:
        out = out + mlp(p["shared"], x, cfg)
    return out, aux
