"""Analytic per-device memory model for every cell — the "does it fit"
complement to XLA:CPU's pessimistic buffer assignment (DESIGN.md §6).

Everything except activation working set is *exact*: parameter, optimizer
and cache bytes are computed from the real pytrees via ``jax.eval_shape``
and divided by each leaf's actual shard count from the rules engine (so
replicated-on-model leaves, padded experts, fsdp fallbacks are all
accounted exactly).  Activation carries use the block-remat formula
(L × microbatch × S × d × 2 B bf16 + f32 working set of one layer).

    PYTHONPATH=src python -m repro.launch.memory_model [--mesh pod]
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import math

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, LONG_CONTEXT_ARCHS, SHAPES, get_config
from jax import set_mesh
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import default_grad_accum, default_opt_config
from repro.models import transformer as T
from repro.sharding import rules
from repro.train.state import train_state_shape

HBM_PER_CHIP = 16e9      # v5e


def _sharded_bytes(shape_tree, shardings) -> float:
    """Σ per-device shard bytes, using each leaf's actual NamedSharding
    (replicated-on-model leaves, expert padding, fsdp fallbacks exact)."""
    leaves = jax.tree.leaves(shape_tree)
    shards = jax.tree.leaves(shardings,
                             is_leaf=lambda x: hasattr(x, "num_devices"))
    total = 0.0
    for l, s in zip(leaves, shards):
        shard_shape = s.shard_shape(l.shape)
        total += math.prod(shard_shape) * l.dtype.itemsize
    return total


def cell_memory(arch: str, shape_name: str, mesh) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    B, S = shape.global_batch, shape.seq_len
    out = {"arch": arch, "shape": shape_name}

    params_shape = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    p_sh = rules.param_shardings(params_shape, mesh)
    out["params_gb"] = _sharded_bytes(params_shape, p_sh) / 1e9

    if shape.kind == "train":
        opt = default_opt_config(cfg)
        st = train_state_shape(cfg, opt)
        mu_sh = rules.param_shardings(st.opt_state["mu"], mesh)
        out["moments_gb"] = 2 * _sharded_bytes(st.opt_state["mu"], mu_sh) / 1e9
        out["grads_gb"] = out["params_gb"] * 2   # f32 grads vs bf16 params
        accum = default_grad_accum(cfg, B)
        dp = max(rules._axis_size(mesh, rules.logical_map(mesh)["dp"]), 1)
        mb_tokens = B * S // accum // dp
        # block-remat carries (bf16) + one layer f32 working set
        carries = cfg.num_layers * mb_tokens * cfg.d_model * 2
        work = 6 * mb_tokens * max(cfg.d_model, cfg.moe_d_ff or 0,
                                   cfg.d_ff or 0) * 4
        out["activations_gb"] = (carries + work) / 1e9
        out["total_gb"] = sum(out[k] for k in
                              ("params_gb", "moments_gb", "grads_gb",
                               "activations_gb"))
    else:
        caches = jax.eval_shape(lambda: T.init_cache(cfg, B, S, jnp.bfloat16))
        c_sh = rules.cache_shardings(caches, mesh)
        out["cache_gb"] = _sharded_bytes(caches, c_sh) / 1e9
        dp = max(rules._axis_size(mesh, rules.logical_map(mesh)["dp"]), 1)
        tok = (B * S if shape.kind == "prefill" else B) // dp
        out["activations_gb"] = 8 * tok * cfg.d_model * 2 / 1e9
        out["total_gb"] = (out["params_gb"] + out["cache_gb"]
                           + out["activations_gb"])
    out["fits_16gb"] = out["total_gb"] <= HBM_PER_CHIP / 1e9
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    args = ap.parse_args()
    mesh = make_production_mesh(multi_pod=args.mesh == "multipod")
    print(f"analytic per-device memory, {args.mesh} "
          f"({mesh.devices.size} chips), v5e 16 GB HBM\n")
    hdr = (f"{'arch':24s} {'shape':12s} {'params':>8s} {'opt+grad':>9s} "
           f"{'cache':>7s} {'activ':>7s} {'total':>7s}  fits")
    print(hdr)
    with set_mesh(mesh):
        for arch in ARCHS:
            for sh in SHAPES:
                if sh == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
                    continue
                m = cell_memory(arch, sh, mesh)
                og = m.get("moments_gb", 0) + m.get("grads_gb", 0)
                print(f"{arch:24s} {sh:12s} {m['params_gb']:8.2f} "
                      f"{og:9.2f} {m.get('cache_gb', 0):7.2f} "
                      f"{m['activations_gb']:7.2f} {m['total_gb']:7.2f}  "
                      f"{'YES' if m['fits_16gb'] else 'NO'}")


if __name__ == "__main__":
    main()
