"""HLO analyzer: loop weighting, dot-FLOP accounting, collective payloads."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.launch import hlo_analysis as H


def _stats(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    return H.weighted_stats(c.as_text())


def test_scan_weighted_equals_unrolled():
    d = 128
    W = jax.ShapeDtypeStruct((d, d), jnp.float32)
    x = jax.ShapeDtypeStruct((8, d), jnp.float32)

    def scanned(w, x):
        out, _ = lax.scan(lambda c, _: (c @ w, None), x, None, length=8)
        return out

    def unrolled(w, x):
        for _ in range(8):
            x = x @ w
        return x

    s1, s2 = _stats(scanned, W, x), _stats(unrolled, W, x)
    assert s1.dot_flops == s2.dot_flops == 8 * 2 * 8 * d * d


def test_single_dot_flops():
    a = jax.ShapeDtypeStruct((16, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 8), jnp.float32)
    s = _stats(lambda a, b: a @ b, a, b)
    assert s.dot_flops == 2 * 16 * 32 * 8


def test_nested_scans_multiply():
    d = 64
    x = jax.ShapeDtypeStruct((d, d), jnp.float32)

    def nested(x):
        def inner(c, _):
            return c @ c, None

        def outer(c, _):
            c2, _ = lax.scan(inner, c, None, length=3)
            return c2, None

        out, _ = lax.scan(outer, x, None, length=5)
        return out

    s = _stats(nested, x)
    assert s.dot_flops == 5 * 3 * 2 * d * d * d


def test_elementwise_vector_flops():
    x = jax.ShapeDtypeStruct((100,), jnp.float32)
    s = _stats(lambda x: jnp.tanh(x) + x, x)
    assert s.vector_flops >= 200           # tanh + add, 100 elements each


def test_shape_bytes_parser():
    assert H._shape_bytes("f32[16,1024]") == 16 * 1024 * 4
    assert H._shape_bytes("bf16[4,2,8]{2,1,0}") == 64 * 2
    assert H._shape_bytes("(f32[8], s32[4])") == 32 + 16
    assert H._shape_bytes("pred[]") == 1
    assert H._shape_bytes("f32[16,1024]{1,0:T(8,128)}") == 16 * 1024 * 4


def test_op_line_parser_tuple_with_comments():
    line = ('  %while.5 = (s32[], f32[8,512]{1,0}, /*index=2*/f32[512,512]) '
            'while(%tuple), condition=%cond, body=%body, '
            'backend_config={"known_trip_count":{"n":"24"}}')
    parsed = H._parse_op_line(line)
    assert parsed is not None
    name, shape, opcode, args, attrs = parsed
    assert name == "while.5" and opcode == "while"
    assert "body" in attrs and H._TRIP.search(attrs).group(1) == "24"


def test_roofline_terms_and_dominant():
    ws = H.WeightedStats()
    ws.dot_flops = H.PEAK_FLOPS          # 1 second of MXU
    ws.traffic_bytes = H.HBM_BW * 2      # 2 seconds of HBM
    ws.collective_bytes["all-reduce"] = H.ICI_BW * 0.5
    r = H.roofline(ws, chips=4, model_flops=H.PEAK_FLOPS * 2)
    assert r.dominant == "memory"
    assert r.bound_time_s == pytest.approx(2.0)
    assert r.useful_ratio == pytest.approx(0.5)
    assert H.mfu_fraction(r, 4) == pytest.approx(
        (H.PEAK_FLOPS * 2) / (4 * H.PEAK_FLOPS * 2.0))


def test_collectives_counted_in_spmd_module():
    """A psum inside shard_map lowers to all-reduce ops we must count."""
    import functools
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.core._axes import make_mesh
    mesh = make_mesh((1,), ("data",))

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P())
    def f(x):
        return lax.psum(x, "data")

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((8, 4), jnp.float32)).compile()
    ws = H.weighted_stats(c.as_text())
    assert ws.collective_count["all-reduce"] >= 1
    assert ws.collective_bytes["all-reduce"] > 0
