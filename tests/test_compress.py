"""Tests for the error-bounded base compressors, the edit codec, and the
end-to-end MSS-preserving pipeline."""
import time

import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.compress import (sz_roundtrip, zfp_roundtrip, encode_edits,
                            decode_edits, compress_preserving_mss,
                            decompress_artifact, overall_compression_ratio,
                            overall_bit_rate, psnr)
from repro.compress import szlike
from repro.compress.codec import _varint_decode, _varint_encode
from repro.compress.szlike import (check_int32_range, effective_step,
                                   sz_compress, sz_decompress, sz_inverse,
                                   sz_transform)
from repro.core import verify_preservation
from repro.data import synthetic_field
import jax
import jax.numpy as jnp


@pytest.mark.parametrize("xi", [1e-1, 1e-2, 1e-3])
@pytest.mark.parametrize("shape", [(33, 47), (17, 19, 23)])
def test_sz_error_bound(xi, shape):
    rng = np.random.default_rng(0)
    f = rng.normal(size=shape).astype(np.float32)
    fh, nbytes = sz_roundtrip(f, xi)
    assert fh.shape == f.shape and fh.dtype == f.dtype
    assert np.max(np.abs(f - fh)) <= xi * (1 + 1e-9)
    assert nbytes < f.nbytes  # should actually compress gaussian noise @1e-1
    # determinism
    fh2, _ = sz_roundtrip(f, xi)
    np.testing.assert_array_equal(fh, fh2)


def test_sz_jax_path_matches_host():
    """The jit'd TPU-target transform must agree with the exact host codec
    within its documented int32 range."""
    rng = np.random.default_rng(1)
    f = rng.normal(size=(16, 24)).astype(np.float32)
    xi = 1e-2
    step = 2.0 * xi
    r = np.asarray(sz_transform(jnp.asarray(f), jnp.float32(step)))
    back = np.asarray(sz_inverse(jnp.asarray(r), jnp.float32(step)))
    assert np.max(np.abs(f - back)) <= xi * (1 + 1e-5)


# ---------------------------------------------------------------------------
# device/host codec parity: the arithmetic contract of DESIGN.md §4 —
# sz_inverse(sz_transform(f)) must be BITWISE equal to the f_hat that
# sz_decompress(sz_compress(f)) reconstructs
# ---------------------------------------------------------------------------

def _tie_field(shape, step):
    """Plateaus and values on exact quantization midpoints (k + 1/2)*step —
    the round-half-even edge both paths must take identically."""
    f = np.zeros(shape, np.float32)
    f.reshape(-1)[::3] = np.float32(2.5 * step)
    f.reshape(-1)[1::5] = np.float32(-0.5 * step)
    f[tuple(s // 2 for s in shape)] = np.float32(7 * step)
    return f


def _parity_case(f, xi):
    fh_host = sz_decompress(sz_compress(f, xi))
    step = effective_step(f, xi)
    sj = jnp.asarray(np.asarray(step, f.dtype))
    r = sz_transform(jnp.asarray(f), sj)
    fh_dev = np.asarray(sz_inverse(r, sj))
    assert fh_dev.dtype == f.dtype
    np.testing.assert_array_equal(fh_host, fh_dev)
    # and the device residual codes re-encode to the identical blob
    blob = szlike.sz_encode_residuals(np.asarray(r), f.shape, f.dtype, step)
    assert blob == sz_compress(f, xi)


@pytest.mark.parametrize("xi", [1e-1, 1e-3])
@pytest.mark.parametrize("shape", [(33, 47), (17, 19, 23)])
def test_device_host_codec_parity_f32(shape, xi):
    rng = np.random.default_rng(7)
    _parity_case(rng.normal(size=shape).astype(np.float32), xi)


@pytest.mark.parametrize("shape", [(21, 27), (9, 11, 13)])
def test_device_host_codec_parity_ties_plateaus(shape):
    xi = 0.05
    _parity_case(_tie_field(shape, 2 * xi), xi)
    _parity_case(np.zeros(shape, np.float32), xi)          # all-plateau


@pytest.mark.parametrize("shape", [(21, 27), (9, 11, 13)])
def test_device_host_codec_parity_f64(shape):
    """f64 parity needs f64 device arithmetic — run the jit path under
    x64 mode (the device pipeline only auto-selects f64 when x64 is on)."""
    from jax import enable_x64
    rng = np.random.default_rng(8)
    f = rng.normal(size=shape)
    assert f.dtype == np.float64
    with enable_x64():
        _parity_case(f, 1e-3)


def test_int32_range_precondition_checked():
    """The szlike docstring promises a runtime check of the int32 range
    precondition — both directly and through the device pipeline."""
    f = np.array([[1e9, -1e9], [5e8, 0.0]], np.float32)
    with pytest.raises(ValueError, match="device path precondition"):
        check_int32_range(f, 1e-3)
    with pytest.raises(ValueError, match="device path precondition"):
        sz_transform(f, np.float32(2e-3))
    with pytest.raises(ValueError, match="device_path=True"):
        compress_preserving_mss(f, 1e-3, device_path=True)
    # f64 fields get the looser int32 limit: 2^21 < ratio < 2^28 passes
    f64 = f.astype(np.float64)
    check_int32_range(f64, 100.0)               # ratio 1e7: ok for f64
    with pytest.raises(ValueError, match="int32 cumsum"):
        check_int32_range(f64, 1e-3)            # ratio 1e12: overflows
    # auto mode classifies the f32 field as host-path-only
    from repro.compress.pipeline import _device_path_reason
    reason, step = _device_path_reason(f, 1e-3, "szlike", "fused")
    assert reason is not None and "precondition" in reason and step is None
    with pytest.raises(ValueError, match="positive"):
        check_int32_range(f, 0.0)


@pytest.mark.parametrize("xi", [1e-1, 1e-2, 1e-3])
@pytest.mark.parametrize("shape", [(32, 48), (16, 20, 24), (33, 47)])
def test_zfp_error_bound(xi, shape):
    rng = np.random.default_rng(0)
    f = rng.normal(size=shape).astype(np.float32)
    fh, nbytes = zfp_roundtrip(f, xi)
    assert fh.shape == f.shape
    assert np.max(np.abs(f - fh)) <= xi * (1 + 1e-9)


def test_zfp_constant_field():
    f = np.full((8, 8), 3.25, np.float32)
    fh, _ = zfp_roundtrip(f, 1e-3)
    assert np.max(np.abs(f - fh)) <= 1e-3


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(0, 500))
def test_edit_codec_roundtrip(seed, n):
    rng = np.random.default_rng(seed)
    idx = np.sort(rng.choice(10_000, size=n, replace=False)).astype(np.int64)
    val = rng.normal(size=n).astype(np.float32)
    blob = encode_edits(idx, val)
    idx2, val2 = decode_edits(blob)
    np.testing.assert_array_equal(idx, idx2)
    np.testing.assert_array_equal(val, val2)


def test_varint_decode_vectorized_roundtrip_guard():
    """Round-trip microbenchmark guard for the vectorized LEB128 decode:
    the former per-byte Python loop took several seconds on a million-edit
    stream; the numpy scan must stay well under the wall-clock budget
    (generous enough for slow CI, ~10x above the vectorized time)."""
    rng = np.random.default_rng(12)
    deltas = rng.integers(0, 2 ** 40, size=1_000_000, dtype=np.int64)
    deltas[::3] = rng.integers(0, 100, size=deltas[::3].size)  # mixed widths
    buf = _varint_encode(deltas)
    # best-of-3: a single sample flakes under full-suite load (VM
    # scheduler stalls), while the regression this guards against — the
    # per-byte Python loop — is slow on every run
    elapsed = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        got = _varint_decode(buf, deltas.size)
        elapsed = min(elapsed, time.perf_counter() - t0)
    np.testing.assert_array_equal(got, deltas)
    assert elapsed < 3.0, f"varint decode regressed: {elapsed:.2f}s for 1M"
    # boundary widths: 1-byte, 2-byte, and full-uint63 values
    edge = np.array([0, 1, 127, 128, 16383, 16384, 2 ** 62], np.int64)
    np.testing.assert_array_equal(_varint_decode(_varint_encode(edge),
                                                 edge.size), edge)
    with pytest.raises(ValueError, match="truncated varint"):
        _varint_decode(_varint_encode(edge)[:-1], edge.size)


def test_edit_codec_bf16_mode():
    idx = np.array([3, 77, 1024], np.int64)
    val = np.array([-0.5, -0.125, -3.0], np.float32)  # bf16-exact values
    blob = encode_edits(idx, val, "bf16")
    idx2, val2 = decode_edits(blob)
    np.testing.assert_array_equal(idx, idx2)
    np.testing.assert_array_equal(val, val2)


@pytest.mark.parametrize("base", ["szlike", "zfplike"])
def test_pipeline_preserves_mss(base):
    f = synthetic_field("molecular", shape=(20, 20, 12), seed=3)
    xi = 0.02 * float(np.ptp(f))
    art = compress_preserving_mss(f, xi, base=base)
    g = decompress_artifact(art)
    v = verify_preservation(f, g, xi)
    assert v["mss_preserved"], v
    assert v["bound_ok"], v
    ocr = overall_compression_ratio(f, art)
    obr = overall_bit_rate(f, art)
    assert ocr > 1.0          # must beat raw storage
    assert 0 < obr < 32.0
    assert psnr(f, g) > 20.0


def test_pipeline_metrics_fields():
    f = synthetic_field("climate", shape=(48, 96), seed=1)
    xi = 1e-2 * float(np.ptp(f))
    art = compress_preserving_mss(f, xi, base="szlike")
    assert art.t_base >= 0 and art.t_fix >= 0
    assert 0 <= art.edit_ratio < 0.5


# ---------------------------------------------------------------------------
# edit-codec hardening (PR 7 bugfixes)
# ---------------------------------------------------------------------------

def _f32_bits(u):
    return np.array([u], np.uint32).view(np.float32)


def test_bf16_rounds_ties_to_even():
    """The old ``(v32 + 0x8000) >> 16`` rounded every halfway case up —
    a systematic magnitude bias. IEEE round-to-nearest-even must leave
    an even result's trailing bit clear on exact ties."""
    # 1.0 + 2^-8: exactly halfway between bf16 0x3F80 (even) and 0x3F81
    tie_down = _f32_bits(0x3F808000)
    # next representable up from 0x3F81: halfway, odd lsb -> round UP to
    # 0x3F82 (even); plain truncation would give 0x3F81
    tie_up = _f32_bits(0x3F818000)
    # just above a tie must still round up
    above = _f32_bits(0x3F808001)
    idx = np.array([1, 2, 3], np.int64)
    blob = encode_edits(idx, np.concatenate([tie_down, tie_up, above]),
                        value_dtype="bf16")
    _, out = decode_edits(blob)
    got = out.view(np.uint32) >> 16
    assert got.tolist() == [0x3F80, 0x3F82, 0x3F81], \
        [hex(g) for g in got]


def test_bf16_preserves_nan_and_inf():
    """NaN payloads in the low mantissa bits must not decay to Inf (the
    +0x8000 carry used to ripple into the exponent), negative NaNs must
    not wrap to +0 via uint32 overflow, and Inf stays Inf."""
    vals = np.concatenate([
        _f32_bits(0x7F800001),      # +NaN, payload only in dropped bits
        _f32_bits(0xFF800001),      # -NaN (old code: uint32 wrap -> +0)
        _f32_bits(0x7F800000),      # +Inf
        _f32_bits(0xFF800000),      # -Inf
        _f32_bits(0x7FC00000),      # quiet NaN with surviving payload
    ])
    blob = encode_edits(np.arange(5), vals, value_dtype="bf16")
    _, out = decode_edits(blob)
    assert np.isnan(out[0])
    assert np.isnan(out[1]) and np.signbit(out[1])
    assert np.isposinf(out[2])
    assert np.isneginf(out[3])
    assert np.isnan(out[4])


def test_bf16_error_bound_unchanged_for_finite_values():
    rng = np.random.default_rng(3)
    val = rng.normal(size=256).astype(np.float32)
    idx = np.arange(val.size, dtype=np.int64)
    _, out = decode_edits(encode_edits(idx, val, value_dtype="bf16"))
    # RNE halves the worst case vs truncation: <= 2^-9 relative
    rel = np.abs(out - val) / np.maximum(np.abs(val), 1e-30)
    assert np.max(rel) <= 2.0 ** -8


def test_decode_edits_rejects_truncated_and_overlong_blobs():
    idx = np.array([5, 9, 100], np.int64)
    val = np.array([1.0, 2.0, 3.0], np.float32)
    blob = encode_edits(idx, val)
    i2, v2 = decode_edits(blob)                    # the intact blob is fine
    np.testing.assert_array_equal(i2, idx)
    with pytest.raises(ValueError, match="length mismatch"):
        decode_edits(blob[:-1])                    # truncated value stream
    with pytest.raises(ValueError, match="length mismatch"):
        decode_edits(blob[:len(blob) // 2])        # truncated mid-stream
    with pytest.raises(ValueError, match="truncated"):
        decode_edits(blob[:10])                    # shorter than the header
    with pytest.raises(ValueError, match="length mismatch"):
        decode_edits(blob + b"\x00")               # trailing garbage


def test_varint_decode_rejects_trailing_values():
    enc = _varint_encode(np.array([1, 2, 3], np.int64))
    np.testing.assert_array_equal(_varint_decode(enc, 3), [1, 2, 3])
    with pytest.raises(ValueError, match="over-long"):
        _varint_decode(enc, 2)                     # a whole extra value
    with pytest.raises(ValueError, match="truncated"):
        _varint_decode(enc, 4)
    with pytest.raises(ValueError, match="over-long"):
        _varint_decode(enc + b"\x05", 3)           # dangling terminated byte
    with pytest.raises(ValueError, match="over-long"):
        _varint_decode(enc + b"\x80", 3)           # dangling continuation
    with pytest.raises(ValueError, match="0 values"):
        _varint_decode(b"\x07", 0)
