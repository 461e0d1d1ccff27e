"""The check that decides ``correct``: the program's answers pass it, and
the control and each fault that a cell can have fail it.  The runs here
skip the look for a chip and drive the rest of a run at a small size."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness
from bench.tests.conftest import ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


def run(root, cell, seed=2 ** 31 + 5):
    return harness.run_cell(jax, harness.load_cell(cell, root), seed, 0.4,
                            False, t0=0.0, root=root)


@pytest.mark.parametrize("cell", CELLS)
def test_the_program_passes(tiny_root, cell):
    r = run(tiny_root, cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"] == {"wrong_answers": {"value": 0, "limit": 0}}
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in harness.load_cell(
        cell, tiny_root).end_to_end}


@pytest.mark.parametrize("cell", CELLS)
def test_the_bf16_control_fails(tiny_root, cell):
    reading = control.control_reading(harness.load_cell(cell, tiny_root),
                                      2 ** 31 + 5, 8, tiny_root)
    assert reading["wrong_answers"] > 0 and reading["limit"] == 0


def altered_frontier(real):
    """The p2p engine with its answer moved one ulp where it is made."""
    def solve(ops, source, *, target=None, **kw):
        d, pred, sw, e, conv = real(ops, source, target=target, **kw)
        d = d.at[target].set(jnp.nextafter(d[target], jnp.inf))
        return d, pred, sw, e, conv
    return solve


def altered_batch(real):
    """The batch engine with one entry of its last row moved one ulp."""
    def solve(ops, sources, **kw):
        D, sw, conv = real(ops, sources, **kw)
        return D.at[-1, 1].set(jnp.nextafter(D[-1, 1], jnp.inf)), sw, conv
    return solve


def half_batch(real):
    """The batch engine solving only the first half of its sources; the
    rows of the rest are copies of the solved ones."""
    def solve(ops, sources, **kw):
        half = max(1, sources.shape[0] // 2)
        D, sw, conv = real(ops, sources[:half], **kw)
        idx = np.arange(sources.shape[0]) % half
        return D[idx], sw, conv
    return solve


def unchanged_frontier(real):
    """The p2p engine returning its labels as they started: the source at
    0, every other vertex unreached."""
    def solve(ops, source, *, target=None, **kw):
        d, pred, sw, e, conv = real(ops, source, target=target, **kw)
        return jnp.full_like(d, jnp.inf).at[source].set(0), pred, sw, e, conv
    return solve


def unchanged_batch(real):
    """The batch engine returning its rows as they started."""
    def solve(ops, sources, **kw):
        D, sw, conv = real(ops, sources, **kw)
        D0 = jnp.full_like(D, jnp.inf)
        return D0.at[jnp.arange(D.shape[0]), sources].set(0), sw, conv
    return solve


@pytest.mark.parametrize("cell,engine,fault", [
    ("tableii-40k.p2p", "sssp_frontier", altered_frontier),
    ("tableii-40k.p2p", "sssp_frontier", unchanged_frontier),
    ("tableii-40k.batch", "sssp_multisource_csr", altered_batch),
    ("tableii-40k.batch", "sssp_multisource_csr", half_batch),
    ("tableii-40k.batch", "sssp_multisource_csr", unchanged_batch),
], ids=["p2p-answer-altered", "p2p-state-unchanged", "batch-answer-altered",
        "batch-half-left-out", "batch-state-unchanged"])
def test_a_broken_timed_path_fails(tiny_root, monkeypatch, cell, engine,
                                   fault):
    from repro.serve import scheduler

    monkeypatch.setattr(scheduler, engine,
                        fault(getattr(scheduler, engine)))
    r = run(tiny_root, cell)
    assert not r["correct"]
    assert r["checks"]["wrong_answers"]["value"] > 0
    assert r["failed"] == r["checks"]["wrong_answers"]["value"]
