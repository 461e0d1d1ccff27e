"""Logical-axis → mesh-axis sharding rules with divisibility fallback.

MaxText-style: every tensor dim carries an ordered preference list of
*logical* axes; a logical axis resolves to one or more mesh axes ("dp" →
("pod", "data") on the multi-pod mesh); an assignment is taken only if the
dim is divisible by the product of the mesh-axis sizes and no mesh axis is
used twice in one spec.  Anything unassigned is replicated — e.g. gemma3's
4 Q-heads on a 16-way model axis fall back to replicated heads while FFN
and vocab stay 16-way tensor-parallel, and qwen2-moe's 60 experts fall back
to sharding the expert FFN dim instead.

Scheme (baseline):
  batch        -> dp  = ("pod", "data")
  heads/ff/vocab/experts -> tp = ("model",)
  param non-TP dim       -> fsdp = ("pod", "data")   (ZeRO-3-style)
  decode KV cache        -> batch over dp, kv-heads over tp,
                            sequence over dp when batch=1 (long_500k).
"""
from __future__ import annotations

import math
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, get_abstract_mesh
from jax.sharding import PartitionSpec as P


def logical_map(mesh: Mesh) -> dict[str, tuple[str, ...]]:
    names = mesh.axis_names
    return {
        "dp": tuple(a for a in ("pod", "data") if a in names),
        "data": tuple(a for a in ("data",) if a in names),
        "pod": tuple(a for a in ("pod",) if a in names),
        "tp": tuple(a for a in ("model",) if a in names),
    }


def _axis_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def assign_spec(shape: Sequence[int], prefs: Sequence[Sequence[str]],
                mesh: Mesh) -> P:
    """prefs[i] = ordered logical-axis candidates for dim i."""
    lm = logical_map(mesh)
    used: set[str] = set()
    out: list[Any] = [None] * len(shape)
    for i, cands in enumerate(prefs):
        for logical in cands:
            axes = lm.get(logical, ())
            if not axes or any(a in used for a in axes):
                continue
            if shape[i] % _axis_size(mesh, axes) != 0:
                continue
            out[i] = axes if len(axes) > 1 else axes[0]
            used.update(axes)
            break
    return P(*out)


# ---------------------------------------------------------------------------
# parameter rules (matched on leaf name; see models/* for layouts)
# ---------------------------------------------------------------------------

_PARAM_RULES: dict[str, list[list[str]]] = {
    # name: prefs per dim (excluding any leading scan-rep dim)
    "tok":      [["tp"], ["dp"]],                    # (V, d)
    "lm_head":  [["dp"], ["tp"]],                    # (d, V)
    "wq":       [["dp"], ["tp"], []],                # (d, H, hd)
    "wk":       [["dp"], ["tp"], []],
    "wv":       [["dp"], ["tp"], []],
    "attn_wo":  [["tp"], [], ["dp"]],                # (H, hd, d)
    "bq":       [["tp"], []],
    "bk":       [["tp"], []],
    "bv":       [["tp"], []],
    "wi_gate":  [["dp"], ["tp"]],                    # (d, ff)
    "wi_up":    [["dp"], ["tp"]],
    "mlp_wo":   [["tp"], ["dp"]],                    # (ff, d)
    "router":   [["dp"], []],                        # (d, E)
    "moe_wi":   [["tp"], ["dp"], ["tp"]],            # (E, d, ff) E->tp else ff
    "moe_wo":   [["tp"], ["tp"], ["dp"]],            # (E, ff, d)
    "in_proj":  [["dp"], ["tp"]],                    # (d, 2di+2N+H)
    "out_proj": [["tp"], ["dp"]],                    # (di, d)
    "conv_w":   [[], ["tp"]],                        # (k, conv_dim)
    "conv_b":   [["tp"]],
}

_MOE_LEAVES = {"wi_gate", "wi_up", "wo"}


def _leaf_rule(path) -> tuple[str, bool]:
    """(rule key, has_leading_rep_dim) from a tree path.

    MoE expert tensors share leaf names with dense MLPs (wi_gate/wi_up/wo);
    they are disambiguated by rank in spec_for_param (expert tensors are
    3-D after stripping the scan-rep dim)."""
    keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
    name = keys[-1]
    in_segment = "segments" in keys or "enc_segments" in keys
    parent = keys[-2] if len(keys) >= 2 else None
    if name == "wo":
        name = "attn_wo" if parent in ("attn", "xattn") else "mlp_wo"
    return name, in_segment


def spec_for_param(path, shape, mesh: Mesh) -> P:
    name, in_segment = _leaf_rule(path)
    dims = list(shape)
    lead = 0
    if in_segment:
        lead = 1
        dims = dims[1:]
    # disambiguate dense-vs-moe expert tensors by rank
    if name in ("wi_gate", "wi_up") and len(dims) == 3:
        name = "moe_wi"
    if name == "mlp_wo" and len(dims) == 3:
        name = "moe_wo"
    prefs = _PARAM_RULES.get(name)
    if prefs is None or len(prefs) != len(dims):
        # norms, scalars, biases, A_log, gates, ... -> replicated
        return P(*([None] * (lead + len(dims))))
    spec = assign_spec(dims, prefs, mesh)
    return P(*([None] * lead + list(spec)))


def param_shardings(params_shape, mesh: Mesh):
    """NamedSharding pytree for a params (or ShapeDtypeStruct) pytree."""
    def f(path, leaf):
        return NamedSharding(mesh, spec_for_param(path, leaf.shape, mesh))
    return jax.tree_util.tree_map_with_path(f, params_shape)


# ---------------------------------------------------------------------------
# activations / batch / cache
# ---------------------------------------------------------------------------

def batch_spec(shape, mesh: Mesh) -> P:
    """Token-like (B, S[, d]) arrays: batch over dp."""
    prefs = [["dp"]] + [[] for _ in shape[1:]]
    return assign_spec(shape, prefs, mesh)


def batch_shardings(batch_shape, mesh: Mesh):
    return jax.tree.map(
        lambda l: NamedSharding(mesh, batch_spec(l.shape, mesh)), batch_shape)


def cache_spec(shape, mesh: Mesh) -> P:
    """KV cache (rep, B, S, KV, hd) / ssm state (rep, B, H, P, N) /
    conv state (rep, B, k-1, conv).  Batch over dp; if batch is
    unshardable (long_500k B=1) the sequence/state dim takes dp;
    kv-heads take tp.

    §Perf hillclimb (EXPERIMENTS.md): when the arch's KV-head count is
    indivisible by the model axis (phi4 kv=8, kimi kv=8, gemma2 kv=4 on a
    16-way axis), the *sequence* dim takes tp instead — split-K/flash-decode
    style cache partitioning.  Without this the scores constraint and the
    S-replicated cache disagree and GSPMD all-gathers the whole cache in
    f32 every decode step (34 GB/step for phi4 decode_32k).  Disable with
    REPRO_NO_CACHE_SEQ_FALLBACK=1 to reproduce the baseline."""
    import os
    if len(shape) >= 4:
        prefs = [[], ["dp"], ["dp"], ["tp"], []][: len(shape)]
        while len(prefs) < len(shape):
            prefs.append([])
        if (len(shape) >= 5
                and not os.environ.get("REPRO_NO_CACHE_SEQ_FALLBACK")):
            lm = logical_map(mesh)
            tp = lm.get("tp", ())
            kv_ok = tp and shape[3] % _axis_size(mesh, tp) == 0
            if not kv_ok:
                prefs[2] = ["dp", "tp"]     # sequence takes the model axis
        return assign_spec(shape, prefs, mesh)
    return assign_spec(shape, [[]] + [["dp"]] * (len(shape) - 1), mesh)


def cache_shardings(cache_shape, mesh: Mesh):
    return jax.tree.map(
        lambda l: NamedSharding(mesh, cache_spec(l.shape, mesh)), cache_shape)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# in-model activation constraints
# ---------------------------------------------------------------------------

_ACT_RULES: dict[str, list[list[str]]] = {
    # (B, S, d) hidden states: batch over dp
    "hidden": [["dp", "data", "pod"], [], []],
    # (B, S, H, hd) projected heads: batch over dp, heads over tp
    "heads": [["dp", "data", "pod"], [], ["tp"], []],
    # (B, S, ff) FFN intermediate: batch over dp, ff over tp
    "ffh": [["dp", "data", "pod"], [], ["tp"]],
    # (B, c, V) logits: batch over dp, vocab over tp
    "logits": [["dp", "data", "pod"], [], ["tp"]],
    # (E, C, d) / (E, C, ff) MoE expert buffers: experts over tp
    "experts": [["tp"], [], []],
    # (G, E, C, d|ff) grouped MoE dispatch buffers: groups over dp,
    # experts over tp (falls back to replicated experts when E indivisible;
    # the expert einsum then partitions over ff via the weight sharding)
    "moe_buffer": [["dp", "data", "pod"], ["tp"], [], []],
    # (G, Tg, d) grouped token buffers
    "tokens_grouped": [["dp", "data", "pod"], [], []],
    # (B, KV, G, Sq, Tk) attention scores: kv-heads over tp; when the
    # arch's KV count is indivisible (gemma3: KV=1) the *key* axis takes
    # tp instead — context-parallel attention (softmax partials reduced
    # by GSPMD), which also split-K-parallelizes long-context decode.
    "scores": [["dp", "data", "pod"], ["tp"], [], [], ["tp"]],
    # (B, H, Sq, Tk) merged-head scores (expanded-KV path): heads over tp
    "scores_h": [["dp", "data", "pod"], ["tp"], [], []],
    # (T, d) flat token buffers (MoE dispatch): tokens over dp
    "tokens_flat": [["dp", "data", "pod"], []],
}


def dp_size() -> int:
    """Size of the ambient mesh's data-parallel axes (1 off-mesh)."""
    am = get_abstract_mesh()
    if am is None or not am.axis_names:
        return 1
    return math.prod(am.shape[a] for a in ("pod", "data")
                     if a in am.axis_names)


def tp_size() -> int:
    """Size of the ambient mesh's model axis (1 off-mesh)."""
    am = get_abstract_mesh()
    if am is None or "model" not in am.axis_names:
        return 1
    return am.shape["model"]


def constrain(x, rule: str):
    """with_sharding_constraint against the ambient mesh; no-op outside a
    mesh context (keeps model code mesh-agnostic — smoke tests run as-is)."""
    am = get_abstract_mesh()
    if am is None or not am.axis_names:
        return x
    prefs = _ACT_RULES[rule]
    if len(prefs) != x.ndim:
        return x
    spec = assign_spec(x.shape, prefs, am)
    return jax.lax.with_sharding_constraint(x, spec)
