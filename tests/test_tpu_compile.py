"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jax, and it compiles for a chip that is
described rather than present (``jax.experimental.topologies``).  That
catches what interpret-mode tests cannot: a kernel Mosaic refuses (an
unaligned block, an in-kernel gather), a kernel that was interpreted
instead of compiled (no ``tpu_custom_call``), or a program that does not
fit the chip's 16 GB of HBM.  Nothing runs, so these tests say nothing
about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and it keeps it until it exits,
so a test worker that did so while collecting would lock the others out.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.core import csr as C
from repro.core.bellman_csr import sssp_multisource_csr
from repro.core.delta_stepping import (auto_delta, delta_operands,
                                       sssp_delta_stepping)
from repro.core.frontier import frontier_operands, sssp_frontier
from repro.kernels.bucket_relax.ops import bucket_relax_block
from repro.kernels.csr_relax.ops import csr_relax_sweep
from repro.kernels.frontier_relax.ops import frontier_cand_block
from repro.kernels.sssp_relax.ops import relax_sweep

N_ROAD = 2 ** 22        # a 2048 x 2048 road grid
K_ROAD = 8              # its padded in-ELL width
F_CHUNK = 256           # frontier_kernel's chunk (make_frontier_sweep_fn)
N_DENSE = 16384         # chip_smoke.py's dense bellman_kernel graph
HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e host, with JAX's persistent compile
    cache off: an entry compiled here cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _specs(tree, sharding):
    return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding), tree)


def _kernel_call(name, sh):
    """(function, argument specs) of one kernel at chip_smoke's shapes."""
    f32, i32 = jnp.float32, jnp.int32
    dist = _spec((N_ROAD,), f32, sh)
    ell_idx = _spec((N_ROAD, K_ROAD), i32, sh)
    ell_w = _spec((N_ROAD, K_ROAD), f32, sh)
    if name == "csr_relax":
        return (lambda d, i, w: csr_relax_sweep(d, i, w, interpret=False),
                (dist, ell_idx, ell_w))
    if name == "frontier_relax":
        return (lambda d, f, w: frontier_cand_block(d, f, w,
                                                    interpret=False),
                (dist, _spec((F_CHUNK,), i32, sh),
                 _spec((F_CHUNK, K_ROAD), f32, sh)))
    if name == "bucket_relax":
        return (lambda d, i, w, hi: bucket_relax_block(d, i, w, hi,
                                                       interpret=False),
                (dist, ell_idx, ell_w, _spec((), f32, sh)))
    assert name == "sssp_relax"
    return (lambda d, a: relax_sweep(d, a, interpret=False),
            (_spec((N_DENSE,), f32, sh), _spec((N_DENSE, N_DENSE), f32, sh)))


@pytest.mark.parametrize("name", ["csr_relax", "frontier_relax",
                                  "bucket_relax", "sssp_relax"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_call(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("engine", ["frontier", "frontier_target",
                                    "delta_stepping"])
def test_serving_engine_compiles_for_v5e(one_chip, engine):
    cg = C.road_like_csr_graph(64 ** 2, seed=0)
    src = _spec((), jnp.int32, one_chip)
    if engine == "delta_stepping":
        delta = auto_delta(cg)
        lowered = sssp_delta_stepping.lower(
            _specs(delta_operands(cg, delta), one_chip), src,
            _spec((), jnp.float32, one_chip), n=cg.n)
    else:
        extra = ({} if engine == "frontier" else
                 {"target": src, "target_lb": _spec((), jnp.float32,
                                                    one_chip)})
        lowered = sssp_frontier.lower(
            _specs(frontier_operands(cg), one_chip), src, n=cg.n, **extra)
    assert lowered.compile().memory_analysis() is not None


def test_multisource_csr_fits_v5e(one_chip):
    """The batched full-row program at n = 2^20 of the Table II corpus
    (m = 3n undirected edges, 6n arcs) with a 16-source bucket: its
    temporaries hold an (S, arcs) candidate array, so S is what decides
    whether it fits."""
    n, arcs, s = 2 ** 20, 6 * 2 ** 20, 16
    ops = {"src": _spec((arcs,), jnp.int32, one_chip),
           "dst": _spec((arcs,), jnp.int32, one_chip),
           "w": _spec((arcs,), jnp.float32, one_chip),
           "rank": _spec((arcs,), jnp.int32, one_chip),
           "last": _spec((n,), jnp.int32, one_chip),
           "scan_steps": _spec((), jnp.int32, one_chip)}
    ma = sssp_multisource_csr.lower(
        ops, _spec((s,), jnp.int32, one_chip), n=n).compile().memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert 0 < need < HBM_BYTES, need
