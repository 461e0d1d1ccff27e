"""Checkpoint: roundtrip (incl. bf16), atomic commit, async manager,
retention GC, latest-step discovery, corrupted-tmp ignored."""
import os
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import (CheckpointManager, latest_step,
                              restore_checkpoint, save_checkpoint)


def _state():
    return {
        "params": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                   "b": jnp.ones((4,), jnp.bfloat16) * 1.5},
        "opt": {"mu": jnp.zeros((3, 4)), "count": jnp.int32(7)},
    }


def test_roundtrip(tmp_path):
    st = _state()
    save_checkpoint(str(tmp_path), st, 5)
    shape = jax.eval_shape(lambda: _state())
    got, extra = restore_checkpoint(str(tmp_path), shape)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(got)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


def test_latest_step_and_gc(tmp_path):
    st = _state()
    for s in (1, 2, 3, 4):
        save_checkpoint(str(tmp_path), st, s)
    assert latest_step(str(tmp_path)) == 4
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(st, 5, block=True)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert steps == ["step_4", "step_5"]


def test_async_manager_waits(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(_state(), 1)
    mgr.wait()
    assert latest_step(str(tmp_path)) == 1


def test_tmp_dirs_never_visible(tmp_path):
    # a crashed writer leaves tmp.step_N; latest_step must ignore it
    os.makedirs(tmp_path / "tmp.step_9")
    save_checkpoint(str(tmp_path), _state(), 2)
    assert latest_step(str(tmp_path)) == 2
    shape = jax.eval_shape(lambda: _state())
    _, _ = restore_checkpoint(str(tmp_path), shape)    # loads step_2


def test_shape_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), _state(), 1)
    bad = jax.eval_shape(
        lambda: {"params": {"w": jnp.zeros((5, 4)),
                            "b": jnp.zeros((4,), jnp.bfloat16)},
                 "opt": {"mu": jnp.zeros((3, 4)), "count": jnp.int32(0)}})
    with pytest.raises(ValueError, match="shape mismatch"):
        restore_checkpoint(str(tmp_path), bad)


def test_restore_with_shardings(tmp_path):
    """Reshard-on-load: restore with explicit NamedShardings."""
    from repro.core._axes import make_mesh
    from repro.sharding import rules
    mesh = make_mesh((1,), ("data",))
    st = _state()
    save_checkpoint(str(tmp_path), st, 3)
    shape = jax.eval_shape(lambda: _state())
    sh = jax.tree.map(lambda _: rules.replicated(mesh), shape,
                      is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    got, _ = restore_checkpoint(str(tmp_path), shape, shardings=sh)
    assert np.array_equal(np.asarray(got["params"]["w"]),
                          np.asarray(st["params"]["w"]))
