"""Conformance suite for the on-device entropy codec (DESIGN.md §8).

Three implementations of the chunked bitplane packer must agree BIT FOR
BIT on the framed stream — the numpy host mirror (the format's reference
semantics), the jnp codec (reference/sharded backends), and the Pallas
kernel (pallas backends) — because a device-pack artifact written by any
one of them must be readable by all consumers, host decode included.
On top of the kernel identity sit the format-level contracts: SZP1 blobs
round-trip against the DEFLATE SZJ2 codec byte-for-byte at the residual
level (cross-decode equality), artifacts record their codec, truncated
or over-long streams hard-error, and the whole thing holds under
batching and under slab-sharded meshes (1/2/4/8 emulated devices —
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; skipped cleanly
on smaller hosts).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.compress import (compress_preserving_mss,
                            compress_preserving_mss_batch,
                            decompress_artifact_batch,
                            decompress_preserving_mss)
from repro.compress import szlike
from repro.core.backend import resolve_backend
from repro.data import synthetic_field
from repro.distributed import ShardedBackend
from repro.kernels import pack
from repro.launch.mesh import make_data_mesh

N_AVAIL = len(jax.devices())

INT32_MIN, INT32_MAX = np.int32(-2**31), np.int32(2**31 - 1)


def _adversarial_cases():
    """int32 code arrays that stress the bitplane layout: chunk-boundary
    sizes, full-width magnitudes, sign edges, constants, empties."""
    rng = np.random.default_rng(7)
    C = pack.CHUNK
    return {
        "empty": np.zeros(0, np.int32),
        "zeros": np.zeros(3 * C + 11, np.int32),
        "ones": np.ones(C - 1, np.int32),
        "minus_one": np.full(C + 1, -1, np.int32),
        "int32_min": np.full(17, INT32_MIN, np.int32),
        "int32_extremes": np.array(
            [INT32_MIN, INT32_MAX, 0, -1, 1,
             INT32_MIN + 1, INT32_MAX - 1], np.int32),
        "small": rng.integers(-5, 6, size=C // 2).astype(np.int32),
        "mixed_chunks": np.concatenate([
            rng.integers(-3, 4, size=C),             # narrow chunk
            rng.integers(-2**20, 2**20, size=C),     # wide chunk
            np.zeros(C, np.int32),                   # zero chunk (b=0)
            rng.integers(-2**30, 2**30, size=37),    # ragged tail
        ]).astype(np.int32),
        "chunk_exact": rng.integers(-1000, 1000, size=2 * C).astype(np.int32),
        "powers": np.array([-(2**k) for k in range(31)] +
                           [2**k for k in range(31)], np.int32),
    }


@pytest.mark.parametrize("name,codes", sorted(_adversarial_cases().items()))
def test_pack_three_way_bit_identity(name, codes):
    """host mirror == jnp == pallas on words, bits, and n_words."""
    w_h, b_h = pack.pack_codes_host(codes)
    w_j, b_j, n_j = pack.pack_codes_jnp(jnp.asarray(codes))
    w_p, b_p, n_p = pack.pack_codes_pallas(jnp.asarray(codes))
    for tag, (w, b, n) in [("jnp", (w_j, b_j, n_j)),
                           ("pallas", (w_p, b_p, n_p))]:
        assert int(n) == w_h.size, (name, tag)
        np.testing.assert_array_equal(
            np.asarray(w)[:int(n)], w_h, err_msg=f"{name}/{tag} words")
        np.testing.assert_array_equal(
            np.asarray(b), b_h, err_msg=f"{name}/{tag} bits")
    # and every unpacker inverts every packer's stream
    back_h = pack.unpack_codes_host(w_h, b_h, codes.size)
    np.testing.assert_array_equal(back_h, codes)
    back_j = np.asarray(pack.unpack_codes_jnp(
        jnp.asarray(w_h), jnp.asarray(b_h.astype(np.int32)), (codes.size,)))
    np.testing.assert_array_equal(back_j, codes)
    back_p = np.asarray(pack.unpack_codes_pallas(
        jnp.asarray(w_h), jnp.asarray(b_h.astype(np.int32)), (codes.size,)))
    np.testing.assert_array_equal(back_p, codes)


def test_unpack_host_rejects_corrupt_streams():
    codes = np.arange(-600, 600, dtype=np.int32)
    words, bits = pack.pack_codes_host(codes)
    with pytest.raises(ValueError):                    # truncated words
        pack.unpack_codes_host(words[:-1], bits, codes.size)
    with pytest.raises(ValueError):                    # over-long words
        pack.unpack_codes_host(
            np.concatenate([words, words[:1]]), bits, codes.size)
    with pytest.raises(ValueError):                    # bits table missized
        pack.unpack_codes_host(words, bits[:-1], codes.size)
    bad = bits.copy()
    bad[0] = 33                                        # bits out of range
    with pytest.raises(ValueError):
        pack.unpack_codes_host(words, bad, codes.size)


def test_szp1_blob_roundtrip_and_entropy_probe():
    r = np.arange(-130, 126, dtype=np.int64).reshape(16, 16)
    step = 0.25
    sz = szlike.sz_encode_residuals(r, r.shape, np.dtype(np.float32), step)
    dp = szlike.sz_encode_residuals(r, r.shape, np.dtype(np.float32), step,
                                    entropy="device-pack")
    assert szlike.sz_blob_entropy(sz) == "deflate"
    assert szlike.sz_blob_entropy(dp) == "device-pack"
    with pytest.raises(ValueError):
        szlike.sz_blob_entropy(b"JUNKJUNKJUNKJUNK")
    # cross-decode: both codecs reconstruct the identical residual array
    np.testing.assert_array_equal(szlike.sz_decode_residuals(sz)[0],
                                  szlike.sz_decode_residuals(dp)[0])
    np.testing.assert_array_equal(szlike.sz_decode_residuals(dp)[0], r)
    # truncation / trailing garbage hard-error
    with pytest.raises(ValueError):
        szlike.sz_parse_packed(dp[:-3])
    with pytest.raises(ValueError):
        szlike.sz_parse_packed(dp + b"\x00")
    with pytest.raises(ValueError):
        szlike.sz_parse_packed(dp[:20])


@pytest.mark.parametrize("shape", [(8, 8, 8), (12, 10)])
def test_artifact_cross_codec_bitwise(shape):
    """One field, both codecs, host and device paths: every decompression
    route lands on the identical array."""
    f = synthetic_field("nyx", shape=shape, seed=3).astype(np.float32)
    xi = 1e-3 * float(np.ptp(f))
    arts = {}
    for entropy in szlike.ENTROPIES:
        for dev in (True, False):
            a = compress_preserving_mss(f, xi, entropy=entropy,
                                        device_path=dev)
            assert a.entropy == entropy
            assert szlike.sz_blob_entropy(a.base_payload) == entropy
            arts[(entropy, dev)] = a
    # device and host writers of one codec emit identical payloads
    for entropy in szlike.ENTROPIES:
        assert arts[(entropy, True)].base_payload == \
            arts[(entropy, False)].base_payload
    gs = {k: decompress_preserving_mss(a) for k, a in arts.items()}
    ref = gs[("deflate", False)]
    for k, g in gs.items():
        np.testing.assert_array_equal(g, ref, err_msg=str(k))
    # the device read fast path and the forced host read agree too
    g_host = decompress_preserving_mss(arts[("device-pack", True)],
                                       device_path=False)
    np.testing.assert_array_equal(g_host, ref)


def test_artifact_cross_codec_f64():
    from jax import enable_x64
    f = synthetic_field("nyx", shape=(6, 7, 8), seed=5).astype(np.float64)
    xi = 1e-6 * float(np.ptp(f))
    with enable_x64():
        a_sz = compress_preserving_mss(f, xi, entropy="deflate")
        a_dp = compress_preserving_mss(f, xi, entropy="device-pack")
        g_sz = decompress_preserving_mss(a_sz)
        g_dp = decompress_preserving_mss(a_dp)
    assert a_dp.dtype == "float64"
    np.testing.assert_array_equal(g_sz, g_dp)


def test_constant_field_device_pack():
    f = np.full((8, 8, 8), 2.5, np.float32)
    a = compress_preserving_mss(f, 1e-3, entropy="device-pack")
    g = decompress_preserving_mss(a)
    assert np.max(np.abs(g - f)) <= 1e-3 * (1 + 1e-9)


def test_batch_cross_codec_bitwise():
    fields = [synthetic_field("nyx", shape=(8, 8, 8), seed=s)
              .astype(np.float32) for s in range(3)]
    xi = [1e-3 * float(np.ptp(f)) for f in fields]
    solo = [compress_preserving_mss(f, x, entropy="device-pack")
            for f, x in zip(fields, xi)]
    batch = compress_preserving_mss_batch(fields, xi, entropy="device-pack")
    for a, s in zip(batch, solo):
        assert a.base_payload == s.base_payload
        assert a.edit_payload == s.edit_payload
        assert a.entropy == "device-pack"
    want = [decompress_preserving_mss(s) for s in solo]
    got = decompress_artifact_batch(batch)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_entropy_validation_errors():
    f = synthetic_field("nyx", shape=(8, 8, 8), seed=0).astype(np.float32)
    with pytest.raises(ValueError, match="entropy"):
        compress_preserving_mss(f, 1e-3, entropy="huffman")
    with pytest.raises(ValueError, match="szlike"):
        compress_preserving_mss(f, 1e-3, base="zfplike",
                                entropy="device-pack")
    with pytest.raises(ValueError, match="entropy"):
        szlike.sz_encode_residuals(np.zeros(4, np.int64), (4,),
                                   np.dtype(np.float32), 0.1,
                                   entropy="huffman")


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_sharded_pack_matches_host(n_dev):
    if N_AVAIL < n_dev:
        pytest.skip(
            f"needs {n_dev} devices, have {N_AVAIL} (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    mesh = make_data_mesh(n_dev)
    rng = np.random.default_rng(n_dev)
    codes = rng.integers(-2**25, 2**25,
                         size=3 * pack.CHUNK + 100).astype(np.int32)
    be = ShardedBackend(mesh=mesh)
    w, b, n = be.pack_codes(jnp.asarray(codes))
    w_h, b_h = pack.pack_codes_host(codes)
    assert int(n) == w_h.size
    np.testing.assert_array_equal(np.asarray(w)[:int(n)], w_h)
    np.testing.assert_array_equal(np.asarray(b), b_h)
    back = be.unpack_codes(jnp.asarray(w_h),
                           jnp.asarray(b_h.astype(np.int32)), (codes.size,))
    np.testing.assert_array_equal(np.asarray(back), codes)


@pytest.mark.parametrize("n_dev", [2, 4])
def test_sharded_artifact_device_pack_parity(n_dev):
    if N_AVAIL < n_dev:
        pytest.skip(
            f"needs {n_dev} devices, have {N_AVAIL} (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    mesh = make_data_mesh(n_dev)
    f = synthetic_field("nyx", shape=(8, 8, 8), seed=1).astype(np.float32)
    xi = 1e-3 * float(np.ptp(f))
    ref = compress_preserving_mss(f, xi, entropy="device-pack")
    a = compress_preserving_mss(f, xi, entropy="device-pack", mesh=mesh)
    assert a.base_payload == ref.base_payload  # mesh changes execution only
    assert a.edit_payload == ref.edit_payload
    g = decompress_preserving_mss(a, mesh=mesh)
    np.testing.assert_array_equal(g, decompress_preserving_mss(ref))


def test_backend_protocol_entries_agree():
    """reference and pallas backend protocol entries match the host
    mirror on a residual-shaped payload (what the pipeline feeds them)."""
    rng = np.random.default_rng(2)
    codes = rng.integers(-300, 300, size=(9, 9, 9)).astype(np.int32)
    flat = codes.ravel()
    w_h, b_h = pack.pack_codes_host(flat)
    for name in ("reference", "pallas"):
        be = resolve_backend(name, codes.shape, np.dtype(np.float32))
        w, b, n = be.pack_codes(jnp.asarray(codes))
        assert int(n) == w_h.size, name
        np.testing.assert_array_equal(np.asarray(w)[:int(n)], w_h,
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(b), b_h, err_msg=name)
        back = be.unpack_codes(jnp.asarray(w_h),
                               jnp.asarray(b_h.astype(np.int32)),
                               codes.shape)
        np.testing.assert_array_equal(np.asarray(back), codes, err_msg=name)
