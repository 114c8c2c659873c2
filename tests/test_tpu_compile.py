"""Ahead-of-time compiles of the hot-path kernels for a TPU v5e that is
described, not attached: Mosaic's block-shape rules, its lowering
coverage and the kernels' VMEM requests at SDRBench sizes are checked
here, where interpret mode cannot see them. Nothing runs.

The topology is described inside a module fixture (never at import):
only the process that loads the TPU library may use it, and the test
run may have several worker processes."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import grid
from repro.kernels.extrema import extrema_masks_pallas
from repro.kernels.fixpass import fix_pass_pallas
from repro.kernels.lorenzo import lorenzo_quant_pallas
from repro.kernels.pack import CHUNK, pack_codes_pallas, unpack_codes_pallas

SHAPE_3D = (64, 512, 512)      # Nyx planes, a slab count cut for speed
SHAPE_2D = (1800, 3600)        # CESM-ATM slice
SHAPE_PACK = (128, 256, 256)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip lands in the persistent cache but
    cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("shape", [SHAPE_3D, SHAPE_2D])
def test_extrema_compiles_for_v5e(one_chip, shape):
    f = _spec(one_chip, shape, jnp.float32)
    i = _spec(one_chip, shape, jnp.int32)
    _compile(lambda g, M, m, a, b: extrema_masks_pallas(
        g, M, m, a, b, interpret=False), f, i, i, i, i)


@pytest.mark.parametrize("shape", [SHAPE_3D, SHAPE_2D])
def test_fixpass_compiles_for_v5e(one_chip, shape):
    f = _spec(one_chip, shape, jnp.float32)
    i = _spec(one_chip, shape, jnp.int32)
    _compile(lambda g, low, s, d, p, u, n: fix_pass_pallas(
        g, low, s, d, p, u, n, interpret=False), f, f, i, i, i, i, i)


@pytest.mark.parametrize("shape", [SHAPE_3D, SHAPE_2D])
def test_lorenzo_compiles_for_v5e(one_chip, shape):
    f = _spec(one_chip, shape, jnp.float32)
    step = _spec(one_chip, (), jnp.float32)
    _compile(lambda x, s: lorenzo_quant_pallas(x, s, interpret=False),
             f, step)


def test_pack_compiles_for_v5e(one_chip):
    r = _spec(one_chip, SHAPE_PACK, jnp.int32)
    _compile(lambda x: pack_codes_pallas(x, interpret=False), r)


def test_unpack_compiles_for_v5e(one_chip):
    n = 1
    for s in SHAPE_PACK:
        n *= s
    words = _spec(one_chip, (n,), jnp.uint32)
    bits = _spec(one_chip, (-(-n // CHUNK),), jnp.int32)
    _compile(lambda w, b: unpack_codes_pallas(w, b, SHAPE_PACK,
                                              interpret=False), words, bits)


def test_steepest_dirs_fits_v5e_hbm_at_nyx_size(one_chip):
    """The original field's direction codes at 512^3 (the jnp stencil
    behind field_topology and verify_preservation) fit one chip's HBM."""
    f = _spec(one_chip, (512, 512, 512), jnp.float32)
    compiled = grid.steepest_dirs.lower(f).compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < 8 * 2**30
