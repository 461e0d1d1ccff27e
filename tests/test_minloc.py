"""minloc_packed (core/sharded.py): tie-breaking and index-bit-packing
bounds — previously covered only by one multi-device smoke pass in
test_integration.py.

The packed variant rides on two invariants this file pins down directly:

1. non-negative f32 distances (INF included) compare identically to their
   IEEE-754 bit patterns viewed as u32 — so one u32 min over the packed
   pairs is the distance min;
2. any valid vertex index (int32, so <= 2^31 - 1 even at the largest
   addressable n) fits a u32 below the 0xFFFFFFFF tie-break sentinel, so
   the second u32 min picks the smallest index among equal distances.

The P=1 shard_map roundtrips run on the single real CPU device; the
cross-device tie-break cases force 4 host devices in a subprocess like the
other multi-device tests.
"""
import functools
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core._axes import make_mesh
from repro.core.sharded import minloc_allgather, minloc_packed, minloc_pmin

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

I32_MAX = np.iinfo(np.int32).max


def test_f32_bit_pattern_order_matches_float_order():
    """Invariant 1, at the bit level: sorting non-negative f32 (with INF
    and the largest finite float) by u32 bit pattern equals sorting by
    value — the property the one-collective pack relies on."""
    rng = np.random.default_rng(0)
    d = np.concatenate([
        rng.uniform(0, 1e30, 500).astype(np.float32),
        np.float32([0.0, np.inf, np.finfo(np.float32).max,
                    np.finfo(np.float32).tiny, 1e-38, 3.0, 3.0]),
    ])
    bits = d.view(np.uint32)
    assert (d[np.argsort(bits, kind="stable")]
            == d[np.argsort(d, kind="stable")]).all()


def test_index_packing_bounds_at_large_n():
    """Invariant 2: the largest int32 vertex id survives the u32 round
    trip and still loses to the 0xFFFFFFFF sentinel."""
    idx = jnp.int32(I32_MAX)
    as_u32 = idx.astype(jnp.uint32)
    assert int(as_u32) == I32_MAX
    assert int(as_u32) < 0xFFFFFFFF
    assert int(as_u32.astype(jnp.int32)) == I32_MAX


def _run_minloc_p1(fn, d, idx):
    mesh = make_mesh((1,), ("data",))

    @functools.partial(shard_map, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    def run(d, i):
        best, bi = fn(d[0], i[0], "data")
        return best[None], bi[None]

    best, bi = run(jnp.float32([d]), jnp.int32([idx]))
    return float(best[0]), int(bi[0])


@pytest.mark.parametrize("fn", [minloc_allgather, minloc_pmin, minloc_packed])
@pytest.mark.parametrize("d,idx", [
    (0.0, 0),
    (3.5, 7),
    (1e-38, I32_MAX),                  # tiny dist, largest packable index
    (np.float32(np.finfo(np.float32).max), I32_MAX),
    (np.inf, I32_MAX),                 # unreachable-candidate sentinel path
])
def test_minloc_p1_roundtrip_exact(fn, d, idx):
    """P=1 collective roundtrip: the packed bitcasts must return the exact
    distance bits and index, including +inf and extreme magnitudes."""
    best, bi = _run_minloc_p1(fn, d, idx)
    ref = np.float32(d)
    assert (np.isinf(best) and np.isinf(ref)) or np.float32(best) == ref
    assert bi == idx


_MULTIDEV_CODE = """
import functools
import numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core._axes import make_mesh
from repro.core.sharded import minloc_allgather, minloc_packed, minloc_pmin

I32_MAX = np.iinfo(np.int32).max
mesh = make_mesh((4,), ("data",))

def reference(ds, idxs):
    best = np.min(ds)
    cand = [i for d, i in zip(ds, idxs) if d == best]
    return best, min(cand)

CASES = [
    # exact cross-device distance ties -> smallest index must win
    ([5.0, 5.0, 5.0, 7.0], [9, 3, I32_MAX, 1]),
    ([5.0, 5.0, 5.0, 5.0], [I32_MAX, I32_MAX - 1, 4, 4]),
    # large-n regime: all indices above 2^30, near the packing ceiling
    ([2.0, 2.0, 3.0, 2.0], [I32_MAX, I32_MAX - 7, 2**30, I32_MAX - 7]),
    # INF candidates (unreachable) must lose to any finite distance
    ([float("inf"), 8.0, float("inf"), 8.0], [0, I32_MAX, 1, 5]),
    # everything unreachable: agree on distance INF + the index tie-break
    ([float("inf")] * 4, [I32_MAX, 7, I32_MAX, 9]),
    # denormal-vs-zero ordering survives the bitcast
    ([0.0, float(np.finfo(np.float32).tiny), 1.0, 0.0], [8, 0, 1, 2]),
]

for fn in (minloc_allgather, minloc_pmin, minloc_packed):
    @functools.partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=(P(), P()), check_vma=False)
    def run(d, i):
        best, bi = fn(d[0], i[0], "data")
        return best[None], bi[None]

    for ds, idxs in CASES:
        best, bi = run(jnp.float32(ds), jnp.int32(idxs))
        rb, ri = reference(np.float32(ds), idxs)
        got = (float(best[0]), int(bi[0]))
        ok = (np.isinf(got[0]) and np.isinf(rb)) or got[0] == rb
        assert ok and got[1] == ri, (fn.__name__, ds, idxs, got, (rb, ri))
print("MINLOC_TIEBREAK_OK")
"""


@pytest.mark.slow
def test_minloc_tiebreak_multidevice_all_variants_match_reference():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run([sys.executable, "-c", _MULTIDEV_CODE],
                       capture_output=True, text=True, env=env, timeout=600)
    assert "MINLOC_TIEBREAK_OK" in r.stdout, r.stdout + r.stderr
