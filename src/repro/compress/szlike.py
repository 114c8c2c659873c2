"""SZ-like error-bounded lossy compressor (prediction + linear-scaling
quantization), reimplemented with the *dual-quantization* parallel
reformulation used by GPU SZ implementations (cuSZ):

  1. linear-scaling quantization   q = round(f / step),  step = 2*xi_eff
     (|f - step*q| <= xi after headroom, see below)
  2. Lorenzo prediction IN THE INTEGER DOMAIN: the residual is the d-D mixed
     first difference of q, which is exact in integers, so prediction is
     embarrassingly parallel both ways — decompression is d nested cumsums
     (an associative scan) instead of SZ's sequential reconstruction.
  3. residual entropy coding: small residuals -> int8 stream + escape list,
     then DEFLATE (stand-in for SZ's Huffman+ZSTD stage).

This is the paper's 'base compressor #1' baseline, and since the
device-resident pipeline (DESIGN.md §4) the host and device paths share
ONE arithmetic contract per dtype so they are bitwise interchangeable:

  * quantization and reconstruction run in the FIELD'S dtype (f32 fields:
    f32 division/round and f32 multiply — numpy on host, XLA on device —
    both IEEE-754 round-to-nearest-even, so host and device agree bit for
    bit);
  * integer work (Lorenzo residual, cumsum inverse) is exact in any width;
    the host codec uses int64, the device path int32.

The int32 device path (sz_transform/sz_inverse, backed by the Pallas
kernel in repro.kernels.lorenzo) therefore requires the residual codes
and every intermediate cumsum to fit int32: intermediates reach
2^d * max|q| with max|q| ~= max|f|/step, so it requires
max|f|/xi < 2^28 (for the paper's field/bound regimes range(f)/xi and
max|f|/xi coincide within a small factor; both are far below 2^28).
f32 fields bind EARLIER, at max|f|/xi < 2^21: past that the quantization
quotient f/step leaves f32 rounding precision (and past ~2^23 no f32
f_hat can hold the bound at all, so the tighter limit forfeits nothing).
``check_int32_range`` validates the dtype's limit with a clear error —
callers of the device path (compress.pipeline) invoke it at runtime;
``sz_transform`` itself also checks when handed a host (numpy) array.
f64 fields keep f64 host arithmetic; the device transform serves them
only when jax x64 mode is enabled.
"""
from __future__ import annotations

import io
import struct
import zlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

# SZJ2: the dequantization arithmetic runs in the field's dtype (the
# shared host/device contract above). SZJ1 blobs used f64-multiply-then-
# cast and would silently reconstruct a different f_hat — refuse them.
_MAGIC = b"SZJ2"
# SZP1: same header, residuals carried as the chunked-bitplane device
# codec's (bits table, uint32 word stream) instead of DEFLATE chunks
# (repro.kernels.pack; DESIGN.md §8)
_MAGIC_PACK = b"SZP1"

#: residual entropy codecs a blob can carry: "deflate" (SZJ2, host
#: zlib — the compatibility default) and "device-pack" (SZP1, the
#: chunked-bitplane codec that also runs fully on device)
ENTROPIES = ("deflate", "device-pack")

# intermediate cumsums of the int32 inverse reach 2^d * max|q| (d <= 3),
# so max|q| < 2^27  <=>  max|f|/xi < 2^28 keeps everything inside int32
INT32_RANGE_LIMIT = 2.0 ** 28
# f32 fields bind earlier: the quantization quotient f/step must round
# exactly in f32 (quotient < 2^22 keeps the fl-division error under half
# a unit and inside the 2^-22*max|f| headroom), so max|f|/xi < 2^21.
# Beyond ~2^23 an f32 field cannot hold the bound in ANY arithmetic
# (xi drops below max|f|'s ulp) — the limit forfeits no well-posed input.
F32_RANGE_LIMIT = 2.0 ** 21


def device_range_limit(dtype) -> float:
    """max|f|/xi ceiling of the device path for fields of ``dtype``."""
    return F32_RANGE_LIMIT if np.dtype(dtype) == np.float32 \
        else INT32_RANGE_LIMIT


def effective_step(f: np.ndarray, xi: float,
                   amax: Optional[float] = None) -> float:
    """The quantization step actually used for ``f`` at bound ``xi``.

    f32 fields reserve headroom for the dtype-arithmetic reconstruction
    (quantize + reconstruct in f32 costs up to ~3 ulp relative to exact
    arithmetic, hence 2^-22; zfplike reserves its own — smaller,
    half-ulp — headroom for its single final f32 cast), and the step
    itself is an f32-exact value so host and device multiply by the
    identical scalar. ``amax``: pass a precomputed max|f| to skip the
    field scan.
    """
    f = np.asarray(f)
    if f.dtype == np.float32 and f.size:
        if amax is None:
            amax = float(np.max(np.abs(f)))
        xi = max(xi - amax * 2.0 ** -22, xi * 0.5)
    step = np.float64(2.0 * xi)
    if f.dtype == np.float32:
        step = np.float64(np.float32(step))
    return float(step)


def check_int32_range(f: np.ndarray, xi: float,
                      amax: Optional[float] = None) -> None:
    """Validate the device path's range precondition (module docstring):
    quantized magnitudes and their d-D cumsum intermediates must fit
    int32 — max|f|/xi < 2^28 — and f32 fields must additionally keep the
    quantization quotient inside f32 rounding precision — max|f|/xi <
    2^21, the binding limit. Raises ValueError otherwise. ``amax``: pass
    a precomputed max|f| to skip the field scan."""
    f = np.asarray(f)
    if f.size == 0:
        return
    if xi <= 0:
        raise ValueError(f"error bound must be positive, got xi={xi!r}")
    if amax is None:
        amax = float(np.max(np.abs(f)))
    limit = device_range_limit(f.dtype)
    if amax / xi >= limit:
        why = ("the f32 quantization quotient would exceed f32 rounding "
               "precision" if limit == F32_RANGE_LIMIT else
               "quantized codes would overflow the int32 cumsum "
               "reconstruction")
        raise ValueError(
            f"device path precondition violated: max|f|/xi = "
            f"{amax / xi:.3g} >= 2^{int(np.log2(limit))}; {why}. Use the "
            "host path (device_path=False) or a looser error bound.")


# ---------------------------------------------------------------------------
# JAX hot path (TPU target; also what the Pallas kernel in repro.kernels
# implements block-wise). Same arithmetic contract as the host codec:
# bitwise-equal f_hat within the int32 range precondition.
# ---------------------------------------------------------------------------

def _lorenzo_residual_jnp(q: jnp.ndarray) -> jnp.ndarray:
    r = q
    for ax in range(q.ndim):
        shifted = jnp.concatenate(
            [jnp.zeros_like(jax.lax.slice_in_dim(r, 0, 1, axis=ax)),
             jax.lax.slice_in_dim(r, 0, r.shape[ax] - 1, axis=ax)], axis=ax)
        r = r - shifted
    return r


@jax.jit
def _sz_transform_jit(f: jnp.ndarray, step) -> jnp.ndarray:
    q = jnp.round(f / step).astype(jnp.int32)
    return _lorenzo_residual_jnp(q)


def sz_transform(f, step) -> jnp.ndarray:
    """quantize + integer Lorenzo -> int32 residual codes.

    ``step`` should be a scalar of f's dtype (a python float behaves as
    one for f32 fields). Host (numpy) inputs are range-checked against
    the device-range precondition; device-resident or traced callers
    must validate themselves via ``check_int32_range`` — the check is a
    host scan and must not force a device->host pull of the field.
    """
    if isinstance(f, np.ndarray) and not isinstance(step, jax.core.Tracer):
        check_int32_range(f, float(np.asarray(step)) / 2.0)
    return _sz_transform_jit(f, step)


def int32_cumsum(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Exact int32 cumsum along ``axis``. The leading axis of a >= 2D
    array runs as an O(n) ``lax.scan`` with a slab carry — XLA's
    log-depth cumsum rewrite strides badly there (~2x slower at 256^3 on
    CPU) — the rest as XLA's native cumsum. Integer adds are exact, so
    both formulations are bitwise identical."""
    x = x.astype(jnp.int32)      # both branches accumulate in int32
    if axis == 0 and x.ndim > 1:
        def step(c, row):
            s = c + row
            return s, s
        _, out = jax.lax.scan(step, jnp.zeros_like(x[0]), x)
        return out
    return jnp.cumsum(x, axis=axis, dtype=jnp.int32)


@jax.jit
def sz_inverse(r: jnp.ndarray, step) -> jnp.ndarray:
    """int32 residual codes -> reconstructed field, in step's dtype
    (weakly-typed python floats reconstruct f32)."""
    q = r
    for ax in range(r.ndim):
        # mszlint: disable=int32-range -- every codec entry gates on
        # codes_fit_int32/check_int32_range before reaching this decode
        q = int32_cumsum(q, ax)
    step = jnp.asarray(step)
    return q.astype(step.dtype) * step


# ---------------------------------------------------------------------------
# exact host path (what actually backs the byte-level codec)
# ---------------------------------------------------------------------------

def _lorenzo_residual_np(q: np.ndarray) -> np.ndarray:
    if q.size == 0:
        return q
    r = q
    for ax in range(q.ndim):
        pad = np.zeros_like(np.take(r, [0], axis=ax))
        shifted = np.concatenate([pad, np.take(r, range(r.shape[ax] - 1), axis=ax)], axis=ax)
        r = r - shifted
    return r


def _pack_residuals(r: np.ndarray) -> bytes:
    """int8 main stream with int64 escape side-channel, DEFLATE'd."""
    flat = r.reshape(-1).astype(np.int64)
    small = (flat >= -127) & (flat <= 127)
    main = np.where(small, flat, -128).astype(np.int8)
    esc_idx = np.flatnonzero(~small).astype(np.int64)
    esc_val = flat[esc_idx].astype(np.int64)
    payload = io.BytesIO()
    for chunk in (main.tobytes(), esc_idx.tobytes(), esc_val.tobytes()):
        comp = zlib.compress(chunk, 6)
        payload.write(struct.pack("<Q", len(comp)))
        payload.write(comp)
    return payload.getvalue()


def _unpack_residuals(buf: bytes, n: int) -> np.ndarray:
    view = memoryview(buf)
    parts = []
    off = 0
    for _ in range(3):
        (ln,) = struct.unpack_from("<Q", view, off)
        off += 8
        parts.append(zlib.decompress(view[off:off + ln]))
        off += ln
    main = np.frombuffer(parts[0], np.int8).astype(np.int64)
    esc_idx = np.frombuffer(parts[1], np.int64)
    esc_val = np.frombuffer(parts[2], np.int64)
    out = main.copy()
    if esc_idx.size:
        out[esc_idx] = esc_val
    return out[:n]


def check_entropy(entropy: str) -> None:
    """Validate a residual entropy codec name against ``ENTROPIES``."""
    if entropy not in ENTROPIES:
        raise ValueError(
            f"unknown entropy codec {entropy!r}; expected one of "
            f"{ENTROPIES}")


def _szlike_header(magic: bytes, shape: Tuple[int, ...], dtype,
                   step: float) -> bytes:
    dtype = np.dtype(dtype)
    ndim = len(shape)
    size = int(np.prod(shape, dtype=np.int64)) if shape else 1
    hdr = struct.pack("<4sBBdQ", magic, ndim,
                      0 if dtype == np.float32 else 1, float(step), size)
    return hdr + struct.pack(f"<{ndim}Q", *shape)


def sz_encode_residuals(r: np.ndarray, shape: Tuple[int, ...],
                        dtype, step: float, *,
                        entropy: str = "deflate") -> bytes:
    """Serialize Lorenzo residual codes into the self-describing SZ-like
    blob. The single entropy-coding entry point for BOTH paths: the host
    codec packs its own int64 residuals, the device pipeline packs the
    int32 codes pulled off the device — identical codes give identical
    bytes. ``entropy`` picks the residual codec (``ENTROPIES``);
    "device-pack" runs the chunked-bitplane packer's numpy mirror here
    (the device pipeline hands its already-packed stream to
    ``sz_encode_packed`` directly and skips this). The profiler span
    ``msz.encode.residuals`` covers the coding."""
    check_entropy(entropy)
    with TraceAnnotation("msz.encode.residuals"):
        if entropy == "device-pack":
            from ..kernels import pack
            words, bits = pack.pack_codes_host(np.asarray(r))
            return sz_encode_packed(words, bits, shape, dtype, step)
        return _szlike_header(_MAGIC, shape, dtype, step) \
            + _pack_residuals(np.asarray(r))


def sz_encode_packed(words: np.ndarray, bits: np.ndarray,
                     shape: Tuple[int, ...], dtype, step: float, *,
                     chunk: Optional[int] = None) -> bytes:
    """Serialize an already-packed chunked-bitplane stream (from any of
    the ``repro.kernels.pack`` codecs — all bitwise identical) into the
    SZP1 blob: the SZJ2-shaped header, then ``<IIQ`` (chunk size, chunk
    count, word count), the per-chunk bit widths as uint8, and the
    little-endian uint32 word stream. Pure byte assembly — the entropy
    work already happened wherever the stream was packed."""
    from ..kernels import pack
    if chunk is None:
        chunk = pack.CHUNK
    words = np.ascontiguousarray(np.asarray(words, np.uint32))
    bits = np.asarray(bits)
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    n_chunks = -(-n // chunk) if n else 0
    if bits.size != n_chunks:
        raise ValueError(
            f"bit-width table has {bits.size} chunks, expected "
            f"{n_chunks} for shape {shape} at chunk={chunk}")
    sub = struct.pack("<IIQ", chunk, n_chunks, words.size)
    return _szlike_header(_MAGIC_PACK, shape, dtype, step) + sub \
        + bits.astype(np.uint8).tobytes() \
        + words.astype("<u4").tobytes()


def _parse_header(blob: bytes):
    hdr = struct.calcsize("<4sBBdQ")
    if len(blob) < hdr:
        raise ValueError(
            f"truncated SZ-like blob: {len(blob)} bytes, header needs {hdr}")
    magic, ndim, dt, step, size = struct.unpack_from("<4sBBdQ", blob, 0)
    off = hdr
    if len(blob) < off + 8 * ndim:
        raise ValueError(
            f"truncated SZ-like blob: {len(blob)} bytes, {ndim}-d header "
            f"needs {off + 8 * ndim}")
    shape = struct.unpack_from(f"<{ndim}Q", blob, off)
    return magic, tuple(int(s) for s in shape), \
        np.dtype(np.float32 if dt == 0 else np.float64), float(step), \
        int(size), off + 8 * ndim


def sz_blob_entropy(blob: bytes) -> str:
    """Which residual entropy codec an SZ-like blob carries ("deflate"
    or "device-pack") — the read side's codec negotiation hook: callers
    route SZP1 payloads to the on-device unpacker without touching the
    byte stream."""
    magic = bytes(blob[:4])
    if magic == _MAGIC:
        return "deflate"
    if magic == _MAGIC_PACK:
        return "device-pack"
    raise ValueError("not an SZ-like blob")


def sz_parse_packed(blob: bytes
                    ) -> Tuple[np.ndarray, np.ndarray, Tuple[int, ...],
                               np.dtype, float, int]:
    """Split an SZP1 blob into ``(words, bits, shape, dtype, step,
    chunk)`` WITHOUT unpacking codes — pure pointer arithmetic, so the
    device read path can ship words/bits to the accelerator with zero
    host entropy work. Header lengths are validated against
    ``len(blob)``: truncated or over-long blobs are hard errors."""
    magic, shape, dtype, step, size, off = _parse_header(blob)
    if magic != _MAGIC_PACK:
        raise ValueError("not a packed (SZP1) SZ-like blob")
    sub = struct.calcsize("<IIQ")
    if len(blob) < off + sub:
        raise ValueError(
            f"SZP1 blob is {len(blob)} bytes, too short for its "
            "pack sub-header (truncated blob)")
    chunk, n_chunks, n_words = struct.unpack_from("<IIQ", blob, off)
    off += sub
    expect_chunks = (-(-size // chunk) if size else 0) if chunk else -1
    if n_chunks != expect_chunks:
        raise ValueError(
            f"SZP1 header: {n_chunks} chunks inconsistent with "
            f"{size} codes at chunk={chunk}")
    end = off + n_chunks + 4 * n_words
    if end != len(blob):
        raise ValueError(
            f"SZP1 blob is {len(blob)} bytes, header demands {end} "
            "(truncated or over-long blob)")
    bits = np.frombuffer(blob, np.uint8, n_chunks, off).astype(np.int32)
    words = np.frombuffer(blob, "<u4", n_words, off + n_chunks)
    words = words.astype(np.uint32, copy=False)
    return words, bits, shape, dtype, step, int(chunk)


def sz_compress(f: np.ndarray, xi: float, *,
                entropy: str = "deflate") -> bytes:
    """Compress with absolute error bound xi. Self-describing blob;
    ``entropy`` picks the residual codec (see ``ENTROPIES``)."""
    f = np.asarray(f)
    if f.dtype not in (np.float32, np.float64):
        raise TypeError(f"float field expected, got {f.dtype}")
    if xi <= 0:
        # linear-scaling quantization has no lossless mode: step = 2*xi
        # degenerates and q = round(f/0) is garbage, so fail loudly here
        # instead of emitting a blob that cannot hold any bound
        raise ValueError(
            f"error bound must be positive for the SZ-like codec, got "
            f"xi={xi!r} (linear-scaling quantization has no lossless mode)")
    step = effective_step(f, xi)
    if f.dtype == np.float32:
        # canonical f32 arithmetic — bitwise-shared with the device path
        q = np.round(f / np.float32(step)).astype(np.int64)
    else:
        q = np.round(f.astype(np.float64) / step).astype(np.int64)
    r = _lorenzo_residual_np(q)
    return sz_encode_residuals(r, f.shape, f.dtype, step, entropy=entropy)


def sz_decode_residuals(blob: bytes
                        ) -> Tuple[np.ndarray, Tuple[int, ...], np.dtype,
                                   float]:
    """Entropy-decode an SZ-like blob into ``(r, shape, dtype, step)``
    WITHOUT reconstructing: ``r`` is the int64 Lorenzo residual-code
    array. This is the host half of the device decompression path
    (DESIGN.md §5) — the byte-stream-sequential DEFLATE decode runs once
    on the host, and everything downstream (cumsum reconstruction,
    dequantization, edit scatter) can stay on device. Dispatches on the
    blob magic: SZP1 (device-pack) payloads decode through the packer's
    numpy mirror, so every consumer of this function reads both codecs
    transparently."""
    magic, shape, dtype, step, size, off = _parse_header(blob)
    if magic == _MAGIC_PACK:
        from ..kernels import pack
        words, bits, shape, dtype, step, chunk = sz_parse_packed(blob)
        r = pack.unpack_codes_host(words, bits, size, chunk) \
            .astype(np.int64).reshape(shape)
        return r, shape, dtype, step
    if magic != _MAGIC:
        raise ValueError("not an SZ-like blob")
    r = _unpack_residuals(blob[off:], size).reshape(shape)
    return r, shape, dtype, step


def codes_fit_int32(r: np.ndarray) -> bool:
    """Sound decode-side precondition of the int32 device reconstruction:
    every intermediate of the d nested cumsums (each axis pass's full
    array — whose elements ARE that axis's running prefixes) must fit
    int32. Compress-time artifacts from the device path satisfy this by
    construction (``check_int32_range``); host-path artifacts can carry
    arbitrarily large codes, so the device decode validates the decoded
    stream itself. Two tiers: every intermediate is a box-prefix sum of
    r entries, so ``sum|r| < 2^31`` proves all of them fit in one cheap
    vectorized pass (typical Lorenzo residuals are tiny, so this is the
    common exit); only an inconclusive sum pays the exact int64 cumsum
    sweep per axis — still far cheaper than the DEFLATE decode that
    precedes it."""
    q = np.asarray(r, np.int64)
    if q.size == 0:
        return True
    lim = np.int64(2 ** 31 - 1)
    # f64 total is within ~n*eps relative error; the margin keeps the
    # shortcut strictly sufficient
    total = float(np.sum(np.abs(q), dtype=np.float64))
    if total * (1 + 1e-6) < float(lim):
        return True
    for ax in range(q.ndim):
        q = np.cumsum(q, axis=ax, dtype=np.int64)
        if np.max(np.abs(q)) > lim:
            return False
    return True


def sz_decompress(blob: bytes) -> np.ndarray:
    """Host-side inverse of ``sz_compress``: entropy-decode the
    residual codes and reconstruct f_hat (d nested cumsums + dequant;
    bitwise equal to the device path's ``backend.reconstruct``)."""
    r, shape, dtype, step = sz_decode_residuals(blob)
    q = r
    for ax in range(len(shape)):
        q = np.cumsum(q, axis=ax, dtype=np.int64)
    if dtype == np.float32:
        # canonical f32 reconstruction (matches sz_inverse bit for bit)
        return q.astype(np.float32) * np.float32(step)
    return q.astype(np.float64) * step


def sz_roundtrip(f: np.ndarray, xi: float) -> Tuple[np.ndarray, int]:
    """Compress + decompress in one call: (f_hat, compressed bytes) —
    the bench/test convenience for the SZ-like base."""
    blob = sz_compress(f, xi)
    return sz_decompress(blob), len(blob)
