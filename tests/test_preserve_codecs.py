"""Codec-agnostic preservation conformance suite (DESIGN.md §11).

Every codec registered through ``compress.preserve`` must satisfy the
same contract, judged by the pure-numpy oracle in ``core/ref.py`` — the
single source of truth this suite checks the production stack against:

* decompressed labels bitwise-equal to ``mss_labels_ref`` on the
  ORIGINAL field, for every (codec, backend, ndim, dtype) cell — the
  reference and Pallas backends plus the slab-sharded SPMD backend on
  2/4/8 emulated devices (skipped cleanly below the device count; the
  tier-1 CI matrix runs them under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
* every stored edit delta within the 2*xi slack (|f - f_hat| <= xi and
  |f - g| <= xi bound each side);
* artifacts byte-identical across backends, paths (szlike host vs
  device), and batch vs solo calls;
* magic negotiation: the read side refuses retired blob formats
  (SZJ1/ZFJ1) and metadata/byte-stream disagreements instead of
  misdecoding them.

Also holds the verifier-gap regressions: ``verify_preservation`` on
batched artifacts (stacks go through ``verify_preservation_batch``) and
on ``xi == 0`` zfplike blobs.
"""
import numpy as np
import pytest
import jax

from repro.compress import (check_artifact, compress_preserving_mss,
                            compress_preserving_mss_batch, decode_edits,
                            decode_payload, decompress_artifact,
                            decompress_preserving_mss,
                            get_preserving_codec, payload_codec, szlike,
                            zfplike)
from repro.compress import preserve
from repro.core import ref as R
from repro.core import verify_preservation, verify_preservation_batch
from repro.launch.mesh import make_data_mesh

N_AVAIL = len(jax.devices())

CODECS = ("szlike", "zfplike")
SHAPES = [(9, 10), (5, 6, 4)]
BACKENDS = ("reference", "pallas", "sharded2", "sharded4", "sharded8")


def _field(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=shape).astype(dtype)


def _backend_mesh(spec):
    """Map a matrix cell to (backend, mesh), skipping sharded cells on
    hosts without enough emulated devices."""
    if spec.startswith("sharded"):
        n = int(spec[len("sharded"):])
        if N_AVAIL < n:
            pytest.skip(
                f"needs {n} devices, have {N_AVAIL} (run under "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return "auto", make_data_mesh(n)
    return spec, None


def _assert_conforms(f, art, xi, codec_name):
    """The PreservingCodec contract, judged entirely by the oracle."""
    assert art.base == codec_name
    pc = get_preserving_codec(codec_name)
    assert art.base_magic.encode("ascii") in pc.magics
    g = decompress_artifact(art)
    assert g.dtype == f.dtype and g.shape == f.shape

    # labels of the decompressed field == oracle labels of the ORIGINAL
    Mf, mf = R.mss_labels_ref(f)
    Mg, mg = R.mss_labels_ref(g)
    np.testing.assert_array_equal(Mg, Mf)
    np.testing.assert_array_equal(mg, mf)

    v = R.verify_preservation_ref(f, g, xi)
    assert v["mss_preserved"] and v["bound_ok"], v
    # the production verifier must agree with the oracle verdict
    vp = verify_preservation(f, g, xi)
    assert vp["mss_preserved"] and vp["bound_ok"], vp
    assert vp["right_labeled_ratio"] == v["right_labeled_ratio"] == 1.0

    # each side of an edit moves at most xi away from f -> 2*xi slack
    _, val = decode_edits(art.edit_payload)
    if val.size:
        assert float(np.max(np.abs(val))) <= 2 * xi * (1 + 1e-5)
    return g


@pytest.mark.parametrize("spec", BACKENDS)
@pytest.mark.parametrize("shape", SHAPES, ids=["2d", "3d"])
@pytest.mark.parametrize("codec_name", CODECS)
def test_conformance_f32(codec_name, shape, spec):
    backend, mesh = _backend_mesh(spec)
    f = _field(shape, np.float32, seed=len(shape))
    xi = 0.05
    art = compress_preserving_mss(f, xi, codec=codec_name, backend=backend,
                                  mesh=mesh)
    if mesh is not None:
        assert art.backend == "sharded"
    _assert_conforms(f, art, xi, codec_name)


@pytest.mark.parametrize("spec", ("reference", "pallas", "sharded2"))
@pytest.mark.parametrize("shape", SHAPES, ids=["2d", "3d"])
@pytest.mark.parametrize("codec_name", CODECS)
def test_conformance_f64_under_x64(codec_name, shape, spec):
    from jax import enable_x64
    backend, mesh = _backend_mesh(spec)
    f = _field(shape, np.float64, seed=7 + len(shape))
    xi = 0.03
    with enable_x64():
        art = compress_preserving_mss(f, xi, codec=codec_name,
                                      backend=backend, mesh=mesh)
        g = _assert_conforms(f, art, xi, codec_name)
    assert g.dtype == np.float64
    # f64 fields store f8 edit values under the "auto" dtype policy, so
    # the decode round-trip is bit-exact per element
    idx, val = decode_edits(art.edit_payload)
    assert val.dtype == (np.float64 if idx.size else val.dtype)


# ---------------------------------------------------------------------------
# byte-identity: backends, paths, batch vs solo
# ---------------------------------------------------------------------------

def _bytes(art):
    return (art.base_payload, art.edit_payload)


@pytest.mark.parametrize("codec_name", CODECS)
def test_artifact_bytes_identical_across_backends(codec_name):
    f = _field((9, 10), np.float32, seed=11)
    xi = 0.05
    ref = compress_preserving_mss(f, xi, codec=codec_name,
                                  backend="reference")
    pal = compress_preserving_mss(f, xi, codec=codec_name, backend="pallas")
    assert _bytes(pal) == _bytes(ref)
    if N_AVAIL >= 2:
        sh = compress_preserving_mss(f, xi, codec=codec_name, backend="auto",
                                     mesh=make_data_mesh(2))
        assert _bytes(sh) == _bytes(ref)


def test_szlike_host_device_bytes_identical():
    f = _field((8, 9, 6), np.float32, seed=12)
    xi = 0.05
    dev = compress_preserving_mss(f, xi, codec="szlike", device_path="auto")
    host = compress_preserving_mss(f, xi, codec="szlike", device_path=False)
    assert dev.path == "device" and host.path == "host"
    assert _bytes(dev) == _bytes(host)
    assert dev.base_magic == host.base_magic == "SZJ2"


@pytest.mark.parametrize("codec_name", CODECS)
def test_batch_bytes_identical_to_solo(codec_name):
    fields = [_field((9, 10), np.float32, seed=s) for s in (1, 2, 3)]
    xi = 0.05
    arts = compress_preserving_mss_batch(fields, xi, codec=codec_name)
    assert len(arts) == 3
    for fi, art in zip(fields, arts):
        solo = compress_preserving_mss(fi, xi, codec=codec_name)
        assert _bytes(art) == _bytes(solo)
        assert art.base_magic == solo.base_magic
    # batched artifacts verify member-by-member (the solo verifier
    # rejects stacks; see test_verify_preservation_rejects_4d_stack)
    g_b = np.stack([decompress_artifact(a) for a in arts])
    verdicts = verify_preservation_batch(np.stack(fields), g_b, xi)
    assert all(v["mss_preserved"] and v["bound_ok"] for v in verdicts)


# ---------------------------------------------------------------------------
# magic negotiation / artifact cross-checks
# ---------------------------------------------------------------------------

def test_payload_codec_negotiates_by_magic():
    f = _field((9, 10), np.float32, seed=4)
    assert payload_codec(szlike.sz_compress(f, 0.05)).name == "szlike"
    assert payload_codec(zfplike.zfp_compress(f, 0.05)).name == "zfplike"
    assert payload_codec(
        szlike.sz_compress(f, 0.05, entropy="device-pack")).name == "szlike"


@pytest.mark.parametrize("magic", [b"SZJ1", b"ZFJ1"])
def test_retired_magics_refused(magic):
    with pytest.raises(ValueError, match="refusing retired"):
        payload_codec(magic + b"\x00" * 32)


def test_unknown_magic_lists_readable_formats():
    with pytest.raises(ValueError, match="readable formats"):
        payload_codec(b"XXXX" + b"\x00" * 32)


def test_artifact_base_payload_mismatch_refused():
    f = _field((9, 10), np.float32, seed=5)
    art = compress_preserving_mss(f, 0.05, codec="zfplike")
    art.base = "szlike"     # metadata now disagrees with the byte stream
    with pytest.raises(ValueError, match="belongs to codec"):
        check_artifact(art)
    with pytest.raises(ValueError):
        decompress_artifact(art)


def test_artifact_dtype_mismatch_refused():
    f = _field((9, 10), np.float32, seed=6)
    art = compress_preserving_mss(f, 0.05, codec="zfplike")
    art.dtype = "float64"   # blob records f32; metadata lies
    with pytest.raises(ValueError, match="decodes to"):
        decode_payload(art)


def test_unknown_codec_name_raises():
    f = _field((9, 10), np.float32, seed=6)
    with pytest.raises(KeyError, match="registered"):
        compress_preserving_mss(f, 0.05, codec="nope")


def test_device_pack_artifact_records_szp1_magic():
    f = _field((9, 10), np.float32, seed=13)
    art = compress_preserving_mss(f, 0.05, codec="szlike",
                                  entropy="device-pack")
    assert art.base_magic == "SZP1"
    assert payload_codec(art.base_payload).name == "szlike"
    _assert_conforms(f, art, 0.05, "szlike")


# ---------------------------------------------------------------------------
# verifier gaps: batched artifacts, xi == 0 blobs
# ---------------------------------------------------------------------------

def test_verify_preservation_rejects_4d_stack():
    f_b = np.stack([_field((5, 6, 4), np.float32, seed=s) for s in (1, 2)])
    with pytest.raises(ValueError, match="verify_preservation_batch"):
        verify_preservation(f_b, f_b, 0.1)


def test_verify_preservation_batch_matches_solo():
    fields = [_field((9, 10), np.float32, seed=s) for s in (4, 5)]
    f_b = np.stack(fields)
    g_b = f_b.copy()
    g_b[1, 0, 0] += np.float32(10.0)   # break member 1 only
    verdicts = verify_preservation_batch(f_b, g_b, [0.1, 0.1])
    solos = [verify_preservation(f_b[i], g_b[i], 0.1) for i in range(2)]
    assert verdicts == solos
    assert verdicts[0]["mss_preserved"] and not verdicts[1]["bound_ok"]
    with pytest.raises(ValueError, match="stack"):
        verify_preservation_batch(fields[0], fields[0], 0.1)


def test_szlike_rejects_nonpositive_xi():
    f = _field((9, 10), np.float32, seed=8)
    for xi in (0.0, -1e-3):
        with pytest.raises(ValueError, match="must be positive"):
            szlike.sz_compress(f, xi)
        with pytest.raises(ValueError):
            compress_preserving_mss(f, xi, codec="szlike",
                                    device_path=False)


def test_zfplike_xi_zero_exact_on_representable_field():
    """xi == 0 is legal for the zfplike codec when the field is exactly
    representable under its block-floating-point transform (constant
    blocks); the artifact carries zero edits and verify_preservation
    accepts the bitwise-exact round-trip at xi = 0."""
    f = np.full((8, 8), -7.5, np.float32)
    art = compress_preserving_mss(f, 0.0, codec="zfplike")
    g = decompress_artifact(art)
    np.testing.assert_array_equal(g, f)
    idx, _ = decode_edits(art.edit_payload)
    assert idx.size == 0
    v = verify_preservation(f, g, 0.0)
    assert v["mss_preserved"] and v["bound_ok"] and v["max_abs_err"] == 0.0


def test_zfplike_rejects_negative_xi():
    f = _field((9, 10), np.float32, seed=9)
    with pytest.raises(ValueError, match="negative"):
        zfplike.zfp_compress(f, -1e-3)


def test_zfplike_f64_roundtrip_keeps_dtype_and_tight_bound():
    """The ZFJ2 regression pair: f64 blobs must decode to f64 carrying
    genuine sub-f32 precision (ZFJ1 always cast the reconstruction to
    f32, losing the precision the bound was derived in) and honor bounds
    near the codec's block-floating-point floor (~amax * 2^-25 per
    fractional bit budget; bounds below it surface at derive time)."""
    from jax import enable_x64
    f = _field((6, 7), np.float64, seed=10)
    xi = 3e-7
    fh = zfplike.zfp_decompress(zfplike.zfp_compress(f, xi))
    assert fh.dtype == np.float64
    assert float(np.max(np.abs(f - fh))) <= xi
    # the reconstruction is NOT an f32-representable field: the ZFJ1
    # read path could not have produced these bytes
    assert not np.array_equal(fh, fh.astype(np.float32).astype(np.float64))
    with enable_x64():
        art = compress_preserving_mss(f, xi, codec="zfplike")
        _assert_conforms(f, art, xi, "zfplike")


# ---------------------------------------------------------------------------
# device read path + service/stream integration with the codec alias
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec_name", CODECS)
def test_decompress_preserving_mss_serves_any_codec(codec_name):
    f = _field((8, 9), np.float32, seed=14)
    art = compress_preserving_mss(f, 0.05, codec=codec_name)
    g_host = decompress_artifact(art)
    np.testing.assert_array_equal(
        decompress_preserving_mss(art), g_host)


def test_service_codec_alias_matches_one_shot():
    from repro.serve import CompressionService, ServiceConfig
    f = _field((9, 10), np.float32, seed=15)
    xi = 0.05
    svc = CompressionService(ServiceConfig(max_batch=2, coalesce_ms=0.5))
    try:
        art = svc.compress(f, xi, codec="zfplike")
        solo = compress_preserving_mss(f, xi, codec="zfplike")
        assert _bytes(art) == _bytes(solo)
        assert art.base == "zfplike" and art.base_magic == "ZFJ2"
        np.testing.assert_array_equal(svc.decompress(art),
                                      decompress_artifact(solo))
    finally:
        svc.close()


def test_registry_rejects_malformed_codecs():
    with pytest.raises(ValueError, match="4 bytes"):
        preserve.register_preserving_codec(preserve.PreservingCodec(
            name="bad", compress=lambda f, xi: b"", decompress=lambda p: None,
            magics=(b"TOOLONG!",)))
    with pytest.raises(ValueError, match="no payload magics"):
        preserve.register_preserving_codec(preserve.PreservingCodec(
            name="bad", compress=lambda f, xi: b"", decompress=lambda p: None,
            magics=()))
