"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt --ckpt-every 10

Production behaviors exercised end-to-end (and covered by tests):
  * checkpoint/restart: atomic async checkpoints; on start, the driver
    resumes from the newest checkpoint and replays the data pipeline from
    the restored step (deterministic, restart-safe);
  * failure injection: ``--simulate-failure-at N`` raises mid-run; rerun
    the same command and training continues from the last checkpoint —
    the integration test asserts bit-identical losses vs an uninterrupted
    run;
  * preemption: SIGTERM triggers a final synchronous checkpoint before
    exit (the TPU-pod eviction pattern);
  * straggler watchdog: per-step wall time is tracked against an EWMA;
    steps slower than ``--straggler-factor``× the moving average are
    logged with their step index (on real pods this feeds re-dispatch);
  * elastic restore: checkpoints store logical arrays; restoring onto a
    different mesh/device count just works (reshard-on-load).
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--data-axis", type=int, default=None)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--simulate-failure-at", type=int, default=None)
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    ap.add_argument("--ddp-compress", action="store_true",
                    help="use the shard_map DP trainer with int8 EF "
                         "gradient compression")
    args = ap.parse_args(argv)

    from jax import set_mesh
    from repro.checkpoint import CheckpointManager, latest_step, restore_checkpoint
    from repro.configs import get_config, make_smoke
    from repro.data.pipeline import DataConfig, SyntheticPipeline
    from repro.launch.mesh import make_host_mesh
    from repro.sharding import rules
    from repro.train.optimizer import OptConfig
    from repro.train.state import init_train_state, train_state_shape
    from repro.train.step import make_ddp_train_step, make_train_step

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = make_smoke(cfg)
    opt_cfg = OptConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5 + 1),
                        total_steps=args.steps)
    mesh = make_host_mesh(data=args.data_axis, model=args.model_axis)

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=args.batch, seed=args.seed,
                    image_tokens=cfg.num_image_tokens,
                    frame_len=(args.seq // cfg.audio_downsample
                               if cfg.encoder_segments else 0),
                    d_model=cfg.d_model)
    pipe = SyntheticPipeline(dc)

    # ---- init or restore -------------------------------------------------
    start_step = 0
    state_shape = train_state_shape(cfg, opt_cfg)
    with set_mesh(mesh):
        if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
            shardings = jax.tree.map(
                lambda l: rules.replicated(mesh), state_shape,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            state, extra = restore_checkpoint(args.ckpt_dir, state_shape,
                                              shardings=shardings)
            start_step = int(extra.get("step", int(state.step)))
            print(f"[train] restored step {start_step} from {args.ckpt_dir}",
                  flush=True)
        else:
            state = init_train_state(jax.random.PRNGKey(args.seed), cfg,
                                     opt_cfg)

        step_fn = jax.jit(make_train_step(cfg, opt_cfg,
                                          grad_accum=args.grad_accum),
                          donate_argnums=0)

        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

        # ---- SIGTERM preemption hook ----------------------------------
        preempted = {"flag": False}

        def _on_sigterm(signum, frame):
            preempted["flag"] = True
        signal.signal(signal.SIGTERM, _on_sigterm)

        # ---- loop -------------------------------------------------------
        ewma = None
        losses = []
        for step_idx in range(start_step, args.steps):
            if (args.simulate_failure_at is not None
                    and step_idx == args.simulate_failure_at):
                # save nothing NEW: the point is recovering from the last
                # periodic checkpoint.  Do drain the in-flight async write
                # first — the injection tests restart determinism, not
                # mid-write interruption (test_tmp_dirs_never_visible covers
                # that separately), and otherwise whether the periodic save
                # landed depends on a disk-vs-step-time race.
                if ckpt:
                    ckpt.wait()
                raise RuntimeError(
                    f"[train] simulated node failure at step {step_idx}")
            batch = {k: jnp.asarray(v) for k, v in
                     pipe.batch_at(step_idx).items()}
            t0 = time.time()
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt
            if dt > args.straggler_factor * ewma and step_idx > start_step + 3:
                print(f"[watchdog] straggler step {step_idx}: "
                      f"{dt:.3f}s vs ewma {ewma:.3f}s", flush=True)
            losses.append(loss)
            if step_idx % args.log_every == 0:
                print(f"[train] step {step_idx} loss {loss:.4f} "
                      f"({dt*1e3:.0f} ms)", flush=True)
            if ckpt and (step_idx + 1) % args.ckpt_every == 0:
                ckpt.save(state, step_idx + 1, {"step": step_idx + 1})
            if preempted["flag"]:
                print("[train] SIGTERM: checkpointing and exiting", flush=True)
                if ckpt:
                    ckpt.save(state, step_idx + 1, {"step": step_idx + 1},
                              block=True)
                sys.exit(143)

        if ckpt:
            ckpt.save(state, args.steps, {"step": args.steps}, block=True)
        print(f"[train] done: final loss {losses[-1]:.4f} "
              f"(first {losses[0]:.4f})", flush=True)
        if os.environ.get("REPRO_EMIT_LOSSES"):
            print("LOSSES " + json.dumps(losses), flush=True)


if __name__ == "__main__":
    main()
