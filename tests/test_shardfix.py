"""Sharded-parity suite: the slab-sharded SPMD fix loop
(repro.distributed.shardfix) must be BITWISE equal to the single-device
``reference`` and ``pallas`` backends — fields, violation counts, and
iteration counts — across device counts, 2D and 3D, including slab
counts not divisible by the device count.

Multi-device cases need emulated devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the second tier-1
CI job sets this); on a 1-device host they skip cleanly.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (available_backends, derive_edits, derive_edits_batch,
                        field_topology, fused_fix, get_backend,
                        resolve_backend, verify_preservation)
from repro.compress import compress_preserving_mss, decompress_artifact
from repro.distributed import (ShardedBackend, active_data_mesh,
                               data_axis_size, sharded_fix)
from repro.launch.mesh import make_data_mesh

N_AVAIL = len(jax.devices())


def _mesh_or_skip(n_dev: int):
    if N_AVAIL < n_dev:
        pytest.skip(
            f"needs {n_dev} devices, have {N_AVAIL} (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return make_data_mesh(n_dev)


def _pair(shape, seed=0, xi=0.3):
    rng = np.random.default_rng(seed)
    f = rng.normal(size=shape).astype(np.float32)
    fh = (f + rng.uniform(-xi, xi, size=shape) * 0.999).astype(np.float32)
    return f, fh, xi


@functools.lru_cache(maxsize=None)
def _solo_results(shape):
    """Single-device (reference, pallas) trajectories for one test pair."""
    f, fh, xi = _pair(shape, seed=sum(shape))
    topo = field_topology(jnp.asarray(f), xi)
    g_r, it_r, ok_r = fused_fix(jnp.asarray(fh), topo, backend="reference")
    g_p, it_p, ok_p = fused_fix(jnp.asarray(fh), topo, backend="pallas")
    np.testing.assert_array_equal(np.asarray(g_r), np.asarray(g_p))
    assert int(it_r) == int(it_p) and bool(ok_r) and bool(ok_p)
    return f, fh, xi, topo, np.asarray(g_p), int(it_p)


# ---------------------------------------------------------------------------
# registry / resolution
# ---------------------------------------------------------------------------

def test_registry_has_sharded():
    assert "sharded" in available_backends()
    assert get_backend("sharded").name == "sharded"


def test_sharded_unusable_without_mesh_raises():
    be = ShardedBackend()
    if active_data_mesh() is None:
        with pytest.raises(ValueError, match="needs a mesh"):
            be.bind()


def test_auto_selects_sharded_under_active_mesh():
    mesh = _mesh_or_skip(2)
    with mesh:
        assert data_axis_size(active_data_mesh()) == 2
        be = resolve_backend("auto", (8, 6, 10), np.float32)
        assert be.name == "sharded" and be.mesh is not None
    # outside the context the single-device default is unchanged
    assert resolve_backend("auto", (8, 6, 10), np.float32).name == "pallas"
    # explicit mesh wins without a context
    be = resolve_backend("auto", (8, 6, 10), np.float32, mesh=mesh)
    assert be.name == "sharded"
    # a 1-device mesh is NOT worth the SPMD detour in auto mode
    assert resolve_backend("auto", (8, 6, 10), np.float32,
                           mesh=make_data_mesh(1)).name == "pallas"


# ---------------------------------------------------------------------------
# bitwise parity of the full fix loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_dev,shape", [
    (1, (13, 6, 7)),            # degenerate chain (runs on any host)
    (2, (13, 6, 7)),            # 13 slabs over 2 -> pad 1
    (4, (13, 6, 7)),            # pad 3
    (8, (13, 6, 7)),            # pad 3, blocks of 2
    (2, (8, 6, 10)),            # divisible
    (4, (12, 16)),              # 2D, divisible
    (2, (29, 11)),              # 2D, pad 1
    (8, (29, 11)),              # 2D, pad 3
])
def test_sharded_parity_bitwise(n_dev, shape):
    mesh = _mesh_or_skip(n_dev)
    f, fh, xi, topo, g_solo, it_solo = _solo_results(shape)
    g_s, it_s, ok_s = fused_fix(jnp.asarray(fh), topo, backend="sharded",
                                mesh=mesh)
    np.testing.assert_array_equal(np.asarray(g_s), g_solo)
    assert int(it_s) == it_solo
    assert bool(ok_s)


def test_more_devices_than_slabs():
    """8-device chain over a 3-slab field: five devices hold only padding
    and must not perturb the result."""
    mesh = _mesh_or_skip(8)
    f, fh, xi, topo, g_solo, it_solo = _solo_results((3, 5, 6))
    g_s, it_s, ok_s = fused_fix(jnp.asarray(fh), topo, backend="sharded",
                                mesh=mesh)
    np.testing.assert_array_equal(np.asarray(g_s), g_solo)
    assert int(it_s) == it_solo and bool(ok_s)


def test_sharded_fix_direct_entrypoint():
    mesh = _mesh_or_skip(2)
    f, fh, xi, topo, g_solo, it_solo = _solo_results((8, 6, 10))
    g_s, it_s, ok_s = sharded_fix(jnp.asarray(fh), topo, mesh)
    np.testing.assert_array_equal(np.asarray(g_s), g_solo)
    assert int(it_s) == it_solo and bool(ok_s)


def test_single_step_parity():
    """One fused_step through the protocol: sharded == pallas, including
    the violation and moved counts (the loop's condition)."""
    mesh = _mesh_or_skip(4)
    f, fh, xi, topo, _, _ = _solo_results((13, 6, 7))
    g2_p, v_p, m_p = get_backend("pallas").fused_step(jnp.asarray(fh), topo)
    g2_s, v_s, m_s = ShardedBackend(mesh=mesh).fused_step(jnp.asarray(fh),
                                                          topo)
    np.testing.assert_array_equal(np.asarray(g2_p), np.asarray(g2_s))
    assert int(v_p) == int(v_s)
    assert int(m_p) == int(m_s) == int(np.sum(np.asarray(g2_p) != fh))


# ---------------------------------------------------------------------------
# end-to-end: derive_edits / compression artifacts byte-for-byte
# ---------------------------------------------------------------------------

def test_derive_edits_sharded_end_to_end():
    mesh = _mesh_or_skip(4)
    f, fh, xi = _pair((13, 6, 7), seed=17)
    solo = derive_edits(f, fh, xi, backend="pallas")
    res = derive_edits(f, fh, xi, mesh=mesh)
    assert res.backend == "sharded"
    assert res.converged and res.iters == solo.iters
    np.testing.assert_array_equal(res.g, solo.g)
    np.testing.assert_array_equal(res.edits_idx, solo.edits_idx)
    np.testing.assert_array_equal(res.edits_val, solo.edits_val)
    v = verify_preservation(f, res.g, xi)
    assert v["mss_preserved"] and v["bound_ok"], v


def test_compress_artifact_parity():
    """Artifacts from the sharded path are byte-for-byte the single-device
    artifacts (so a sharded compressor farm and a single-chip decompressor
    interoperate freely)."""
    mesh = _mesh_or_skip(2)
    from repro.data import synthetic_field
    f = synthetic_field("molecular", shape=(10, 12, 8), seed=3)
    xi = 0.02 * float(np.ptp(f))
    solo = compress_preserving_mss(f, xi, base="szlike")
    shard = compress_preserving_mss(f, xi, base="szlike", mesh=mesh)
    assert shard.backend == "sharded"
    assert shard.edit_payload == solo.edit_payload
    assert shard.base_payload == solo.base_payload
    g = decompress_artifact(shard)
    v = verify_preservation(f, g, xi)
    assert v["mss_preserved"] and v["bound_ok"], v


def test_derive_edits_batch_sharded_matches_solo():
    mesh = _mesh_or_skip(2)
    shape, xi, B = (8, 6, 10), 0.3, 2
    rng = np.random.default_rng(23)
    fs = np.stack([rng.normal(size=shape).astype(np.float32)
                   for _ in range(B)])
    fhs = np.stack([(fi + rng.uniform(-xi, xi, size=shape) * 0.999)
                    .astype(np.float32) for fi in fs])
    results = derive_edits_batch(fs, fhs, xi, mesh=mesh)
    assert len(results) == B
    for fi, fhi, res in zip(fs, fhs, results):
        assert res.backend == "sharded"
        solo = derive_edits(fi, fhi, xi, backend="pallas")
        np.testing.assert_array_equal(res.g, solo.g)
        assert res.iters == solo.iters and res.converged
