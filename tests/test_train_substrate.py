"""Optimizer / train-step / compression / data / sharding-rules tests."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, make_smoke
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.sharding import rules
from repro.train import compression as comp
from repro.train.optimizer import (OptConfig, adamw_update, clip_by_global_norm,
                                   global_norm, init_opt_state, schedule)
from repro.train.state import init_train_state
from repro.train.step import make_train_step

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_matches_reference_scalar():
    """One AdamW step on a single scalar vs hand-computed values."""
    cfg = OptConfig(lr=0.1, warmup_steps=0, total_steps=10**9, b1=0.9,
                    b2=0.999, eps=1e-8, weight_decay=0.0, clip_norm=1e9)
    params = {"scale": jnp.asarray(2.0)}    # 'scale' -> no weight decay
    opt = init_opt_state(params, cfg)
    grads = {"scale": jnp.asarray(0.5)}
    new_p, new_s, m = adamw_update(grads, opt, params, cfg)
    # bias-corrected first step: update = lr * g/|g| = lr (adam step=sign-ish)
    mu = 0.1 * 0.5
    nu = 0.001 * 0.25
    step = (mu / 0.1) / (np.sqrt(nu / 0.001) + 1e-8)
    assert np.isclose(float(new_p["scale"]), 2.0 - 0.1 * step, rtol=1e-5)
    assert int(new_s["count"]) == 1


def test_schedule_warmup_and_cosine():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=110,
                    min_lr_ratio=0.1)
    assert float(schedule(jnp.asarray(5), cfg)) == pytest.approx(0.5)
    assert float(schedule(jnp.asarray(10), cfg)) == pytest.approx(1.0, rel=1e-3)
    assert float(schedule(jnp.asarray(110), cfg)) == pytest.approx(0.1, rel=1e-3)


def test_clip_by_global_norm():
    g = {"a": jnp.ones((4,)) * 3.0, "b": jnp.ones((4,)) * 4.0}
    assert float(global_norm(g)) == pytest.approx(10.0)
    clipped, gn = clip_by_global_norm(g, 5.0)
    assert float(global_norm(clipped)) == pytest.approx(5.0, rel=1e-5)
    assert float(gn) == pytest.approx(10.0)


def test_weight_decay_mask():
    cfg = OptConfig(lr=1.0, warmup_steps=0, total_steps=10**9,
                    weight_decay=1.0, clip_norm=1e9)
    params = {"w": jnp.asarray(1.0), "scale": jnp.asarray(1.0)}
    opt = init_opt_state(params, cfg)
    grads = {"w": jnp.asarray(0.0), "scale": jnp.asarray(0.0)}
    new_p, _, _ = adamw_update(grads, opt, params, cfg)
    assert float(new_p["w"]) < 1.0          # decayed
    assert float(new_p["scale"]) == 1.0     # masked


def test_train_loss_decreases_and_accum_matches():
    cfg = make_smoke(get_config("qwen1.5-0.5b"))
    opt = OptConfig(lr=1e-3, warmup_steps=2, total_steps=100)
    state = init_train_state(KEY, cfg, opt)
    B, S = 4, 32
    batch = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    step = jax.jit(make_train_step(cfg, opt))
    losses = []
    for _ in range(6):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]

    s1 = init_train_state(KEY, cfg, opt)
    s2 = init_train_state(KEY, cfg, opt)
    s1, m1 = jax.jit(make_train_step(cfg, opt))(s1, batch)
    s2, m2 = jax.jit(make_train_step(cfg, opt, grad_accum=2))(s2, batch)
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))),
        s1.params, s2.params)
    assert max(jax.tree.leaves(d)) < 5e-3


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000) * 3, jnp.float32)
    q, s = comp.quantize_int8(x)
    err = np.abs(np.asarray(comp.dequantize_int8(q, s)) - np.asarray(x))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_error_feedback_preserves_signal():
    """Sum of dequantized transmissions + final error == sum of inputs
    (error feedback never loses gradient mass)."""
    from jax import shard_map
    from repro.core._axes import make_mesh
    mesh = make_mesh((1,), ("data",))
    import functools
    from jax.sharding import PartitionSpec as P

    @functools.partial(shard_map, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    def one_round(g, e):
        return comp.compressed_mean(g, e, "data")

    rng = np.random.default_rng(1)
    gs = [jnp.asarray(rng.standard_normal(64), jnp.float32)
          for _ in range(5)]
    err = jnp.zeros((64,))
    sent = jnp.zeros((64,))
    for g in gs:
        ghat, err = one_round(g, err)
        sent = sent + ghat
    total_in = sum(np.asarray(g) for g in gs)
    assert np.allclose(np.asarray(sent + err), total_in, atol=1e-4)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_restart_safe():
    dc = DataConfig(vocab_size=100, seq_len=16, global_batch=4, seed=7)
    p1, p2 = SyntheticPipeline(dc), SyntheticPipeline(dc)
    b1, b2 = p1.batch_at(13), p2.batch_at(13)
    assert np.array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(p1.batch_at(13)["tokens"],
                              p1.batch_at(14)["tokens"])
    # labels are next-token
    assert np.array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])
    assert (b1["labels"][:, -1] == -1).all()


def test_data_per_host_sharding():
    dc = DataConfig(vocab_size=100, seq_len=8, global_batch=8, seed=0)
    h0 = SyntheticPipeline(dc, process_index=0, process_count=2)
    h1 = SyntheticPipeline(dc, process_index=1, process_count=2)
    assert h0.local_batch == 4
    assert not np.array_equal(h0.batch_at(0)["tokens"],
                              h1.batch_at(0)["tokens"])


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_assign_spec_divisibility_fallback():
    mesh = AbstractMesh((2, 4), ("data", "model"))
    # divisible -> assigned
    assert rules.assign_spec((8, 16), [["dp"], ["tp"]], mesh) == P("data", "model")
    # first dim indivisible -> dropped, second still assigned
    assert rules.assign_spec((7, 16), [["dp"], ["tp"]], mesh) == P(None, "model")
    # axis used once only
    assert rules.assign_spec((8, 8), [["tp"], ["tp"]], mesh) == P("model", None)


def test_param_rules_moe_fallback():
    # production model axis is 16-way: 60 experts are indivisible
    mesh = AbstractMesh((2, 16), ("data", "model"))
    # 60 experts indivisible by 16 -> ff gets the model axis
    import jax.tree_util as jtu
    path = (jtu.DictKey("segments"), jtu.SequenceKey(0), jtu.SequenceKey(0),
            jtu.DictKey("ffn"), jtu.DictKey("wi_gate"))
    spec = rules.spec_for_param(path, (24, 60, 64, 1408), mesh)
    assert spec == P(None, None, "data", "model")
    # 64 experts divisible -> experts take the model axis
    spec = rules.spec_for_param(path, (24, 64, 64, 1408), mesh)
    assert spec == P(None, "model", "data", None)


def test_cache_spec_long_context_batch1():
    mesh = AbstractMesh((2, 4), ("data", "model"))
    # (rep, B=1, S, KV, hd): B unshardable -> S takes dp, KV takes tp
    spec = rules.cache_spec((26, 1, 1024, 4, 256), mesh)
    assert spec == P(None, None, "data", "model", None)
    # (rep, B=128, S, KV, hd): B takes dp, KV takes tp
    spec = rules.cache_spec((26, 128, 1024, 4, 256), mesh)
    assert spec == P(None, "data", None, "model", None)


def test_constrain_noop_outside_mesh():
    x = jnp.ones((4, 8, 16))
    y = rules.constrain(x, "hidden")    # no ambient mesh -> identity
    assert y is x or np.array_equal(np.asarray(x), np.asarray(y))
