"""Jittable train / serve steps.

``make_train_step``: pjit-style step (GSPMD distributes via in/out
shardings chosen by sharding/rules.py): value_and_grad -> clip -> AdamW.
Optional gradient-accumulation microbatching (scan over microbatches with
fp32 accumulators).

``make_ddp_train_step``: an explicit shard_map data-parallel step used to
exercise the int8 error-feedback gradient compression path (params
replicated in the DP group, local grads, compressed mean, identical
updates on every rank).

``make_prefill_step`` / ``make_decode_step``: serving entry points matching
the assigned prefill/decode/long cells.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.models import transformer as T
from repro.train import compression as comp
from repro.train.optimizer import OptConfig, adamw_update
from repro.train.state import TrainState


def make_train_step(cfg, opt_cfg: OptConfig, *, grad_accum: int = 1):
    def loss_fn(params, batch):
        return T.train_loss(params, batch, cfg)

    def train_step(state: TrainState, batch):
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch)
        else:
            B = batch["tokens"].shape[0]
            assert B % grad_accum == 0
            mb = B // grad_accum
            stacked = jax.tree.map(
                lambda x: x.reshape((grad_accum, mb) + x.shape[1:]), batch)
            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)

            def body(carry, microbatch):
                acc_g, acc_l = carry
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state.params, microbatch)
                acc_g = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), acc_g, g)
                return (acc_g, acc_l + l), m

            (grads, loss_sum), ms = lax.scan(
                body, (zero_g, jnp.zeros((), jnp.float32)), stacked)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = loss_sum / grad_accum
            metrics = jax.tree.map(lambda x: jnp.mean(x), ms)

        new_params, new_opt, om = adamw_update(
            grads, state.opt_state, state.params, opt_cfg)
        new_state = TrainState(new_params, new_opt, state.step + 1)
        return new_state, {"loss": loss, **metrics, **om}

    return train_step


def make_ddp_train_step(cfg, opt_cfg: OptConfig, mesh, *, axis: str = "data",
                        compress: bool = True):
    """Explicit-DP step over ``mesh[axis]`` with int8 EF compression."""

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(), P(), P(axis)),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    def step(params, opt_state, err, batch):
        def loss_fn(p):
            loss, m = T.train_loss(p, batch, cfg)
            return loss, m

        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        if compress:
            grads, err = comp.compress_tree(grads, err, axis)
        else:
            grads = jax.tree.map(lambda g: lax.pmean(g, axis), grads)
        new_params, new_opt, om = adamw_update(grads, opt_state, params,
                                               opt_cfg)
        return new_params, new_opt, err, lax.pmean(loss, axis)

    return step


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def make_prefill_step(cfg, *, max_len: int):
    def prefill_step(params, tokens, image_embeds=None, encoder_frames=None):
        return T.prefill(params, tokens, cfg, max_len=max_len,
                         image_embeds=image_embeds,
                         encoder_frames=encoder_frames)
    return prefill_step


def make_decode_step(cfg):
    def decode_step(params, token, pos, caches, image_embeds=None):
        return T.decode_step(params, token, pos, caches, cfg,
                             image_embeds=image_embeds)
    return decode_step
