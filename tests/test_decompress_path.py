"""Device-resident decompression path (DESIGN.md §5).

The contract under test: ``decompress_preserving_mss(art,
device_path=True)`` (and "auto" whenever the preconditions hold) is
BITWISE identical to the host-side ``decompress_artifact`` on every
artifact the compress paths produce — 2D and 3D, f32 and f64-under-x64,
solo and batched, reference/pallas/sharded — while moving at most one
field-sized transfer in each direction, plus the edge cases around the
edit-application and bound-accounting bugfixes that ride with it.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.compress import (compress_preserving_mss, decompress_artifact,
                            decompress_artifact_batch,
                            decompress_preserving_mss, encode_edits, psnr)
from repro.compress import codec, pipeline, szlike, zfplike
from repro import debug
from repro.core import verify_preservation
from repro.core.driver import apply_edits, apply_edits_device
from repro.data import synthetic_field
from repro.launch.mesh import make_data_mesh

N_AVAIL = len(jax.devices())

SHAPES = [(26, 18), (12, 10, 9)]


def _case(shape, seed=3, rel=0.02):
    f = synthetic_field("molecular", shape=shape, seed=seed)
    return f, rel * float(np.ptp(f))


def _artifact(shape, seed=3, rel=0.02, **kw):
    f, xi = _case(shape, seed=seed, rel=rel)
    return f, xi, compress_preserving_mss(f, xi, **kw)


# ---------------------------------------------------------------------------
# bitwise parity host <-> device decode, per backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("shape", SHAPES)
def test_decode_bitwise_identical(shape, backend):
    f, xi, art = _artifact(shape)
    g_host = decompress_artifact(art)
    g_dev = decompress_preserving_mss(art, device_path=True, backend=backend)
    np.testing.assert_array_equal(g_host, g_dev)
    assert g_dev.dtype == f.dtype and g_dev.shape == f.shape
    v = verify_preservation(f, g_dev, xi)
    assert v["mss_preserved"] and v["bound_ok"], v


@pytest.mark.parametrize("shape", SHAPES)
def test_decode_host_path_artifact_parity(shape):
    """Host-produced szlike artifacts (byte-identical to device-produced
    ones here) also decode on device, via the decode-side range check."""
    f, xi, art = _artifact(shape, device_path=False)
    assert art.path == "host"
    np.testing.assert_array_equal(
        decompress_artifact(art),
        decompress_preserving_mss(art, device_path=True))


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
@pytest.mark.parametrize("shape", SHAPES)
def test_decode_sharded_bitwise_identical(shape, n_dev):
    if N_AVAIL < n_dev:
        pytest.skip("needs >= %d devices (run under XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)" % n_dev)
    mesh = make_data_mesh(n_dev)
    f, xi, art = _artifact(shape)
    g = decompress_preserving_mss(art, device_path=True, backend="sharded",
                                  mesh=mesh)
    np.testing.assert_array_equal(decompress_artifact(art), g)


def test_decode_f64_under_x64():
    from jax import enable_x64
    f, xi = _case((12, 10, 9))
    f = f.astype(np.float64)
    with enable_x64():
        art = compress_preserving_mss(f, xi)
        g_host = decompress_artifact(art)
        g_dev = decompress_preserving_mss(art, device_path=True)
        assert g_dev.dtype == np.float64
        np.testing.assert_array_equal(g_host, g_dev)


def test_decode_auto_falls_back():
    f, xi = _case((26, 18))
    # zfplike base: no device reconstruct
    artz = compress_preserving_mss(f, xi, base="zfplike")
    np.testing.assert_array_equal(decompress_preserving_mss(artz),
                                  decompress_artifact(artz))
    with pytest.raises(ValueError, match="device_path=True"):
        decompress_preserving_mss(artz, device_path=True)
    # f64 artifacts need x64 for device arithmetic
    art64 = compress_preserving_mss(f.astype(np.float64), xi)
    assert art64.path == "host"
    np.testing.assert_array_equal(decompress_preserving_mss(art64),
                                  decompress_artifact(art64))


def test_decode_range_check_falls_back():
    """Host-path artifacts whose codes overflow the int32 reconstruction
    must be caught by the decoded-stream check, not silently wrapped.
    Pipeline-produced artifacts only reach this state as f64 (an f32
    field meeting its bound has max|f|/xi < 2^24 < 2^28)."""
    from jax import enable_x64
    rng = np.random.default_rng(0)
    f = 1e7 * (1 + 0.1 * rng.normal(size=(10, 12)))    # f64
    xi = 1e-4      # max|f|/xi ~ 1e11 >> 2^28: int64 host codec only
    art = compress_preserving_mss(f, xi, device_path=False)
    r, _, _, _ = szlike.sz_decode_residuals(art.base_payload)
    assert not szlike.codes_fit_int32(r)
    with enable_x64():
        g = decompress_preserving_mss(art)           # auto -> host fallback
        np.testing.assert_array_equal(g, decompress_artifact(art))
        with pytest.raises(ValueError, match="int32"):
            decompress_preserving_mss(art, device_path=True)


def test_decode_range_check_guards_constructed_f32_artifact():
    """Directly-constructed f32 artifacts bypass the pipeline's compress-
    time bound enforcement, so the decode-side check must catch their
    overflowing codes too (sz_compress happily quantizes a field far
    beyond the bound its blob can reconstruct in f32)."""
    rng = np.random.default_rng(1)
    f = (1e6 * (1 + 0.1 * rng.normal(size=(12, 10)))).astype(np.float32)
    payload = szlike.sz_compress(f, 1e-4)     # max|f|/xi ~ 1e10 >> 2^28
    art = pipeline.CompressedArtifact(
        base="szlike", base_payload=payload,
        edit_payload=encode_edits(np.zeros(0, np.int64),
                                  np.zeros(0, np.float32)),
        shape=f.shape, dtype=str(f.dtype), xi=1e-4)
    r, _, _, _ = szlike.sz_decode_residuals(art.base_payload)
    assert not szlike.codes_fit_int32(r)
    np.testing.assert_array_equal(decompress_preserving_mss(art),
                                  decompress_artifact(art))
    with pytest.raises(ValueError, match="int32"):
        decompress_preserving_mss(art, device_path=True)
    with pytest.raises(ValueError, match="int32"):
        decompress_artifact_batch([art, art], device_path=True)


def test_codes_fit_int32_intermediates():
    # per-element codes fit int32 but the axis-0 cumsum overflows
    r = np.full((3, 2), 2 ** 30, np.int64)
    assert not szlike.codes_fit_int32(r)
    assert szlike.codes_fit_int32(np.zeros((0, 4), np.int64))
    assert szlike.codes_fit_int32(np.ones((5, 5), np.int64))


# ---------------------------------------------------------------------------
# transfer counting: <= 1 field-sized crossing each way
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", SHAPES)
def test_decode_transfer_count(shape, monkeypatch):
    f, xi, art = _artifact(shape)
    log = []
    monkeypatch.setattr(pipeline, "_transfer_hook",
                        lambda d, n: log.append((d, n)))
    decompress_preserving_mss(art, device_path=True)   # warm-up: compiles
    log.clear()
    # guard bans implicit syncs; the hook counts the explicit seams
    with debug.no_transfers():
        decompress_preserving_mss(art, device_path=True)
    field_sized = [(d, n) for d, n in log if n >= f.nbytes]
    assert sum(1 for d, _ in field_sized if d == "h2d") <= 1, log
    assert sum(1 for d, _ in field_sized if d == "d2h") == 1, log


def test_decode_batch_transfer_count(monkeypatch):
    B = 3
    arts = [compress_preserving_mss(
        synthetic_field("molecular", shape=(10, 12, 8), seed=s),
        0.02 * float(np.ptp(synthetic_field("molecular", shape=(10, 12, 8),
                                            seed=s))))
            for s in range(B)]
    log = []
    monkeypatch.setattr(pipeline, "_transfer_hook",
                        lambda d, n: log.append((d, n)))
    decompress_artifact_batch(arts, device_path=True)  # warm-up: compiles
    log.clear()
    with debug.no_transfers():
        decompress_artifact_batch(arts, device_path=True)
    member_bytes = int(np.prod((10, 12, 8))) * 4
    # pipelined: one member-sized h2d per member (residual codes), ONE
    # batch-sized d2h of the stacked g — no duplicate crossings
    h2d = [n for d, n in log if d == "h2d" and n >= member_bytes]
    assert len(h2d) == B, log
    d2h = [n for d, n in log if d == "d2h" and n >= member_bytes]
    assert d2h == [B * member_bytes], log


# ---------------------------------------------------------------------------
# batched decode
# ---------------------------------------------------------------------------

def test_decode_batch_matches_solo():
    B = 4
    arts, hosts = [], []
    for s in range(B):
        f = synthetic_field("molecular", shape=(10, 12, 8), seed=s)
        xi = (0.01 + 0.01 * s) * float(np.ptp(f))    # per-member steps
        arts.append(compress_preserving_mss(f, xi))
        hosts.append(decompress_artifact(arts[-1]))
    for g, h in zip(decompress_artifact_batch(arts, device_path=True), hosts):
        np.testing.assert_array_equal(g, h)


def test_decode_batch_sharded_matches_solo():
    if N_AVAIL < 2:
        pytest.skip("needs >= 2 devices (run under XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    mesh = make_data_mesh(2)
    arts = []
    for s in range(2):
        f = synthetic_field("molecular", shape=(10, 12, 8), seed=s)
        arts.append(compress_preserving_mss(f, 0.02 * float(np.ptp(f))))
    gb = decompress_artifact_batch(arts, device_path=True, backend="sharded",
                                   mesh=mesh)
    for a, g in zip(arts, gb):
        np.testing.assert_array_equal(decompress_artifact(a), g)


def test_decode_batch_heterogeneous_and_empty():
    assert decompress_artifact_batch([]) == []
    f2, xi2, a2 = _artifact((26, 18))
    f3, xi3, a3 = _artifact((12, 10, 9))
    az = compress_preserving_mss(f2, xi2, base="zfplike")
    out = decompress_artifact_batch([a2, a3, az])    # mixed: member-by-member
    for a, g in zip([a2, a3, az], out):
        np.testing.assert_array_equal(decompress_artifact(a), g)


# ---------------------------------------------------------------------------
# edge cases: zero edits, xi == 0 verification, empty/constant zfp fields
# ---------------------------------------------------------------------------

def test_decode_zero_edit_artifact():
    f, xi = _case((12, 10, 9))
    payload = szlike.sz_compress(f, xi)
    art = pipeline.CompressedArtifact(
        base="szlike", base_payload=payload,
        edit_payload=encode_edits(np.zeros(0, np.int64),
                                  np.zeros(0, np.float32)),
        shape=f.shape, dtype=str(f.dtype), xi=xi)
    g_host = decompress_artifact(art)
    np.testing.assert_array_equal(g_host, szlike.sz_decompress(payload))
    np.testing.assert_array_equal(
        g_host, decompress_preserving_mss(art, device_path=True))
    # batched zero-edit members: the padded scatter must be a no-op too
    np.testing.assert_array_equal(
        g_host, decompress_artifact_batch([art, art], device_path=True)[1])


def test_verify_preservation_xi_zero():
    f, _ = _case((10, 12))
    v = verify_preservation(f, f.copy(), 0.0)
    assert v["bound_ok"] and v["mss_preserved"]
    assert v["max_abs_err"] == 0.0
    g = f.copy()
    g[0, 0] += np.float32(1e-3)
    assert not verify_preservation(f, g, 0.0)["bound_ok"]


@pytest.mark.parametrize("shape", [(0, 8), (4, 0, 8)])
def test_zfp_empty_field_roundtrip(shape):
    f = np.zeros(shape, np.float32)
    fh = zfplike.zfp_decompress(zfplike.zfp_compress(f, 1e-3))
    assert fh.shape == f.shape


def test_zfp_constant_field_roundtrip():
    f = np.full((8, 12), -7.5, np.float32)
    fh = zfplike.zfp_decompress(zfplike.zfp_compress(f, 1e-4))
    assert np.max(np.abs(f - fh)) <= 1e-4


def test_sz_empty_field_roundtrip():
    f = np.zeros((0, 6), np.float32)
    fh, _ = szlike.sz_roundtrip(f, 1e-3)
    assert fh.shape == f.shape and fh.dtype == f.dtype


# ---------------------------------------------------------------------------
# the bound-accounting bugfix: zfp's f32-cast headroom (half-ULP, not 2^-22)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rel", [2.0 ** -19, 2.0 ** -16, 1e-3])
def test_zfp_bound_holds_inclusive_of_f32_cast_near_margin(rel):
    """The absolute bound must hold AFTER the final f32 cast, including
    bounds within a few octaves of the f32 representability floor
    (~amax * 2^-23) where the cast headroom dominates the budget."""
    rng = np.random.default_rng(11)
    # large offset: amax >> range, the regime where cast headroom binds
    f = (1000.0 + rng.normal(size=(16, 16))).astype(np.float32)
    amax = float(np.max(np.abs(f)))
    xi = rel * amax
    fh = zfplike.zfp_decompress(zfplike.zfp_compress(f, xi))
    assert fh.dtype == np.float32
    assert float(np.max(np.abs(f.astype(np.float64) - fh))) <= xi


def test_zfp_headroom_not_overreserved():
    """The old amax * 2^-22 reserve ate 8x the true half-ULP cast cost;
    with the correct accounting a bound at 4x the old reserve must not
    lose more than ~the true cast headroom off the effective budget."""
    rng = np.random.default_rng(7)
    f = (100.0 + 0.1 * rng.normal(size=(16, 16))).astype(np.float32)
    amax = float(np.max(np.abs(f)))
    xi = amax * 2.0 ** -20
    fh = zfplike.zfp_decompress(zfplike.zfp_compress(f, xi))
    err = float(np.max(np.abs(f.astype(np.float64) - fh)))
    assert err <= xi


# ---------------------------------------------------------------------------
# the edit-application bugfix: duplicates accumulate / are refused
# ---------------------------------------------------------------------------

def test_apply_edits_duplicate_indices_accumulate():
    f_hat = np.zeros((2, 3), np.float32)
    idx = np.array([1, 1, 4], np.int64)
    val = np.array([0.25, 0.25, -1.0], np.float32)
    g = apply_edits(f_hat, idx, val)
    # buffered fancy += would leave 0.25 at flat index 1
    assert g.reshape(-1)[1] == np.float32(0.5)
    assert g.reshape(-1)[4] == np.float32(-1.0)
    # unsorted but unique still lands on the fast path correctly
    g2 = apply_edits(f_hat, np.array([4, 1]), np.array([1.0, 2.0],
                                                      np.float32))
    assert g2.reshape(-1)[4] == 1.0 and g2.reshape(-1)[1] == 2.0


def test_encode_edits_rejects_duplicates():
    idx = np.array([3, 7, 7], np.int64)
    val = np.ones(3, np.float32)
    with pytest.raises(ValueError, match="duplicate edit index 7"):
        encode_edits(idx, val)
    # unsorted-but-unique is still fine (sorted internally)
    blob = encode_edits(np.array([7, 3], np.int64), val[:2])
    i2, v2 = codec.decode_edits(blob)
    np.testing.assert_array_equal(i2, [3, 7])


def test_apply_edits_device_matches_host():
    rng = np.random.default_rng(5)
    f_hat = rng.normal(size=(9, 8)).astype(np.float32)
    idx = np.sort(rng.choice(f_hat.size, size=12, replace=False))
    val = rng.normal(size=12).astype(np.float32)
    g_host = apply_edits(f_hat, idx, val)
    g_dev = np.asarray(apply_edits_device(jnp.asarray(f_hat), idx, val))
    np.testing.assert_array_equal(g_host, g_dev)
    # out-of-range (padding) indices drop instead of wrapping
    idx_pad = np.concatenate([idx, [f_hat.size, f_hat.size]])
    val_pad = np.concatenate([val, [5.0, 5.0]]).astype(np.float32)
    g_pad = np.asarray(apply_edits_device(jnp.asarray(f_hat), idx_pad,
                                          val_pad))
    np.testing.assert_array_equal(g_host, g_pad)


# ---------------------------------------------------------------------------
# psnr: range normalization (the paper's/SZ's convention)
# ---------------------------------------------------------------------------

def test_psnr_range_normalized_shift_invariant():
    rng = np.random.default_rng(2)
    f = rng.normal(size=(32, 32)).astype(np.float64)
    g = f + 1e-3 * rng.normal(size=f.shape)
    base = psnr(f, g)
    shifted = psnr(f + 1e4, g + 1e4)
    # the old max|f| normalization inflated the shifted case by ~80 dB
    assert abs(base - shifted) < 1e-6
    assert psnr(f, f) == float("inf")
    c = np.full((4, 4), 3.0)
    assert psnr(c, c + 1e-3) == float("-inf")


def test_decode_edits_batch_layout():
    blobs = [encode_edits(np.array([1, 5], np.int64),
                          np.array([0.5, 1.5], np.float32)),
             encode_edits(np.zeros(0, np.int64), np.zeros(0, np.float32)),
             encode_edits(np.array([0, 2, 9], np.int64),
                          np.array([1.0, 2.0, 3.0], np.float32))]
    idx_b, val_b, counts = codec.decode_edits_batch(blobs, fill_idx=10)
    assert idx_b.shape == (3, 3) and val_b.shape == (3, 3)
    np.testing.assert_array_equal(counts, [2, 0, 3])
    np.testing.assert_array_equal(idx_b[1], [10, 10, 10])
    np.testing.assert_array_equal(val_b[0], [0.5, 1.5, 0.0])
    pairs = codec.decode_edits_batch(blobs)
    assert len(pairs) == 3 and pairs[1][0].size == 0
