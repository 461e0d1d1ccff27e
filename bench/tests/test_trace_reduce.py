"""The trace reduction and the roofline arithmetic, pinned on a trace
recorded on a TPU v5e: one Table II batch job (n = 2^20, S = 4) inside
``bench.window`` / ``bench.submit`` / ``bench.tick`` annotations."""
import os
import types

import pytest

from bench import harness, roofline, trace_reduce
from bench.tests.conftest import ROOT

TRACE = os.path.join(os.path.dirname(__file__), "data", "batch_v5e.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_busy_is_the_union_of_leaf_ops_in_the_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(15.464801797)
    assert reduced["busy_s"] == pytest.approx(15.456204973)
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    idle = sum(s for _, s in reduced["idle_gaps"])
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"])


def test_ops_and_modules_are_named_and_ranked(reduced):
    top, sec = reduced["device_ops"][0]
    assert top == "fusion.6 f32[4,1048576] fusion"     # the segment-min
    assert sec == pytest.approx(13.321459184)
    assert not any(name.endswith(" while") for name, _ in
                   reduced["device_ops"])               # control flow out
    times = [s for _, s in reduced["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert reduced["modules"][0] == [
        "jit_sssp_multisource_csr", pytest.approx(15.456218948)]


def test_idle_gaps_are_named_by_the_host_annotation(reduced):
    names = {n for n, _ in reduced["idle_gaps"]}
    assert names <= {"bench.tick", "bench.submit", "bench.window"}
    assert reduced["idle_gaps"][0][0] == "bench.tick"
    assert reduced["idle_by_host"][0][0] == "bench.tick"


def test_op_parts():
    assert trace_reduce.op_parts(
        "%fusion.5 = f32[6291440,4]{0,1:T(4,128)S(1)} fusion(f32[4] %a), "
        "kind=kCustom") == ("fusion.5 f32[6291440,4] fusion", "fusion")
    assert trace_reduce.op_parts("%while.1 = (s32[], f32[8]) while(%t)")[1] \
        == "while"


def test_least_bytes_on_a_tiny_csr():
    # n = 3, 4 arcs, 2 rows: indptr 4*(3+1) + arcs 8*4 + rows 4*2*3
    assert roofline.batch_least_bytes(3, 4, 2) == 16 + 32 + 24
    assert roofline.batch_least_bytes(2 ** 20, 6291440, 4) == 71303044


def test_peaks_table():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_roofline_reader_on_the_recorded_trace(reduced):
    span = types.SimpleNamespace(name="batch_solve", t0=0.0, t1=1.0,
                                 args={"B": 4, "occupancy": 1.0,
                                       "sweeps": 32})
    ctx = {"trace": reduced, "spans": [span],
           "graph": {"n": 2 ** 20, "arcs": 6291440},
           "device_kind": "TPU v5 lite"}
    share = harness.reader("multisource_csr_roofline", ROOT)(ctx)
    assert share == pytest.approx(
        100 * 71303044 / 819e9 / 15.456218948)
    assert 0 < share < 100
    per_sweep = harness.reader("sweep_device_ms.batch", ROOT)(ctx)
    assert per_sweep == pytest.approx(15.456204973 / 32 * 1e3)
    idle = harness.reader("device_idle_pct.batch", ROOT)(ctx)
    assert idle == pytest.approx(
        100 * (1 - 15.456204973 / 15.464801797))
    assert harness.reader("device_idle_pct.p2p", ROOT)(ctx) is None
