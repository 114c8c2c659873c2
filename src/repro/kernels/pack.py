"""On-device entropy codec for the SZ-like int32 residual codes
(DESIGN.md §8): chunked bitplane / fixed-length packing.

DEFLATE was the last pipeline stage still running on the host after the
device-resident compress (§4) and decompress (§5) paths landed — and the
sole reason the stream scheduler needs worker-thread pools. This module
replaces it with a device codec in the TopoSZp mold (lightweight,
embarrassingly parallel, no byte-sequential state):

* the flat code array splits into fixed ``CHUNK``-code chunks;
* each chunk zigzag-maps its codes to uint32 (small magnitudes of either
  sign become small unsigned values) and keeps only the ``b`` lowest
  bitplanes, where ``b`` is the bit length of the chunk's max magnitude
  — Lorenzo residuals are tiny almost everywhere, so most chunks store
  a handful of planes and a constant chunk stores none;
* plane ``k`` of a chunk is the k-th bit of all ``CHUNK`` codes,
  transposed into ``CHUNK/32`` uint32 words (bit ``t`` of word ``m`` is
  code ``m*32+t``'s bit ``k``), so a chunk occupies exactly
  ``b * CHUNK/32`` words of the output stream;
* chunk output offsets are an exclusive parallel prefix sum over the
  per-chunk word counts (``szlike.int32_cumsum`` — the PR-4 slab-carry
  scan — is the building block), followed by one scatter that compacts
  the worst-case-dense per-chunk regions into the final stream.

Three bitwise-identical implementations share this layout contract:

* ``pack_codes_pallas`` / ``unpack_codes_pallas`` — the production
  kernels: each grid program takes a block of chunks, every chunk a
  (32, CHUNK/32) int32 tile, and computes their bit widths and 32
  transposed planes with static loops and sublane sums (VPU vector
  ops; the blocks span their last two dimensions, as Mosaic's tiling
  rule asks). The offset scan + compaction scatter stay XLA-level
  around the kernel — a hand-rolled Pallas scan would only re-derive
  ``int32_cumsum``.
* ``pack_codes_jnp`` / ``unpack_codes_jnp`` — pure-jnp twins (the
  ``reference`` backend, and what the ``sharded`` backend runs on its
  global arrays: every per-chunk stage is independent, so GSPMD
  partitions it for free).
* ``pack_codes_host`` / ``unpack_codes_host`` — the numpy mirror that
  backs the byte-level blob codec in ``compress.szlike`` (host-path
  artifacts, conformance tests). All integer arithmetic, so host and
  device agree bit for bit.

Everything is exact integer work — no rounding contract needed. The
full int32 range round-trips, including ``INT32_MIN`` (zigzag
``0xFFFFFFFF``, 32 planes).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .extrema import default_interpret

#: codes per chunk — one bit-width decision (and one pallas grid
#: program) per CHUNK codes; must stay a multiple of 32 so bitplanes
#: transpose into whole uint32 words
CHUNK = 1024


def words_per_plane(chunk: int = CHUNK) -> int:
    """uint32 words one bitplane of a ``chunk``-code chunk occupies."""
    if chunk % 32:
        raise ValueError(f"chunk must be a multiple of 32, got {chunk}")
    return chunk // 32


# ---------------------------------------------------------------------------
# shared jnp building blocks (also what the pallas wrappers compose with)
# ---------------------------------------------------------------------------

def _zigzag_jnp(r: jnp.ndarray) -> jnp.ndarray:
    """int32 -> uint32 zigzag map (0,-1,1,-2,.. -> 0,1,2,3,..); exact
    bit-level twin of ``_zigzag_np``."""
    zz = jnp.bitwise_xor(r << 1, r >> 31)          # int32 wrap is defined
    return jax.lax.bitcast_convert_type(zz, jnp.uint32)


def _unzigzag_jnp(u: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``_zigzag_jnp``: uint32 -> int32."""
    v = (u >> jnp.uint32(1)) ^ (jnp.uint32(0) - (u & jnp.uint32(1)))
    return jax.lax.bitcast_convert_type(v, jnp.int32)


def _chunk_layout(n: int, chunk: int) -> Tuple[int, int, int]:
    """(n_chunks, padded length, words/plane) of an ``n``-code stream."""
    wpp = words_per_plane(chunk)
    n_chunks = -(-n // chunk) if n else 0
    return n_chunks, n_chunks * chunk, wpp


def _offsets_jnp(bits: jnp.ndarray, wpp: int):
    """(exclusive word offsets, total words) from per-chunk bit widths,
    via the ``int32_cumsum`` slab-carry scan (exact in int32: the stream
    is at most n_codes words, and code counts fit int32 by the device
    path's own size regime)."""
    from ..compress.szlike import int32_cumsum
    words = bits * jnp.int32(wpp)
    # mszlint: disable=int32-range -- per-chunk word counts are bounded
    # by the stream length (<= n_codes words), which fits int32 by the
    # device path's own size regime
    ends = int32_cumsum(words, 0)
    return ends - words, ends[-1] if bits.size else jnp.int32(0)


def _pack_planes_jnp(u3: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(dense, bits) of zigzagged chunks ``u3`` (n_chunks, wpp, 32):
    ``dense`` (n_chunks, 32*wpp) holds every chunk's 32 transposed
    bitplanes plane-major, ``bits`` the per-chunk bit widths."""
    n_chunks, wpp, _ = u3.shape
    maxu = jnp.max(u3, axis=(1, 2)) if n_chunks else \
        jnp.zeros((0,), jnp.uint32)
    bits = (jnp.uint32(32) - jax.lax.clz(maxu)).astype(jnp.int32)
    t = jax.lax.broadcasted_iota(jnp.uint32, (n_chunks, wpp, 32), 2)
    planes = [jnp.sum(((u3 >> jnp.uint32(k)) & jnp.uint32(1)) << t,
                      axis=2, dtype=jnp.uint32) for k in range(32)]
    dense = jnp.stack(planes, axis=1).reshape(n_chunks, 32 * wpp)
    return dense, bits


def _unpack_planes_jnp(dense: jnp.ndarray, wpp: int) -> jnp.ndarray:
    """Inverse of ``_pack_planes_jnp``: dense (n_chunks, 32*wpp) with
    absent planes zero-filled -> zigzagged codes (n_chunks, wpp, 32)."""
    n_chunks = dense.shape[0]
    d3 = dense.reshape(n_chunks, 32, wpp)
    t = jax.lax.broadcasted_iota(jnp.uint32, (n_chunks, wpp, 32), 2)
    u3 = jnp.zeros((n_chunks, wpp, 32), jnp.uint32)
    for k in range(32):
        u3 = u3 | (((d3[:, k, :, None] >> t) & jnp.uint32(1))
                   << jnp.uint32(k))
    return u3


def _compact_jnp(dense: jnp.ndarray, bits: jnp.ndarray, wpp: int):
    """Scatter the per-chunk dense regions into the compact stream:
    (words[capacity], n_words). Capacity is the b=32 worst case (one
    word per code); callers slice to ``n_words`` after a host sync."""
    n_chunks, region = dense.shape
    cap = n_chunks * region
    offsets, n_words = _offsets_jnp(bits, wpp)
    j = jnp.arange(region, dtype=jnp.int32)
    valid = j[None, :] < (bits * jnp.int32(wpp))[:, None]
    gidx = jnp.where(valid, offsets[:, None] + j[None, :], jnp.int32(cap))
    out = jnp.zeros((cap,), jnp.uint32)
    out = out.at[gidx.reshape(-1)].add(
        jnp.where(valid, dense, jnp.uint32(0)).reshape(-1), mode="drop")
    return out, n_words


def _expand_jnp(words: jnp.ndarray, bits: jnp.ndarray, wpp: int
                ) -> jnp.ndarray:
    """Gather each chunk's words out of the compact stream into the
    zero-filled dense layout ``_unpack_planes_jnp`` consumes. ``words``
    may be the exact ``n_words``-long stream — invalid lanes gather
    clipped and are masked to zero."""
    n_chunks = bits.shape[0]
    region = 32 * wpp
    offsets, _ = _offsets_jnp(bits, wpp)
    j = jnp.arange(region, dtype=jnp.int32)
    valid = j[None, :] < (bits * jnp.int32(wpp))[:, None]
    gidx = offsets[:, None] + j[None, :]
    # one sentinel word so an all-constant stream (zero words total)
    # still has a gatherable axis; valid lanes never reach it
    padded = jnp.concatenate([words, jnp.zeros((1,), jnp.uint32)])
    gathered = jnp.take(padded, gidx, mode="clip")
    return jnp.where(valid, gathered, jnp.uint32(0))


# ---------------------------------------------------------------------------
# pure-jnp codec (reference backend; sharded runs it on global arrays)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk",))
def pack_codes_jnp(r: jnp.ndarray, chunk: int = CHUNK):
    """Pack int32 residual codes into the chunked-bitplane stream.

    Returns ``(words, bits, n_words)``: ``words`` a capacity-sized
    uint32 array (jit outputs are static-shaped; only the first
    ``n_words`` entries are the stream — slice after a host sync),
    ``bits`` the per-chunk widths (int32), ``n_words`` the stream
    length as a device scalar.
    """
    n = r.size
    n_chunks, n_pad, wpp = _chunk_layout(n, chunk)
    if n_chunks == 0:
        return (jnp.zeros((0,), jnp.uint32), jnp.zeros((0,), jnp.int32),
                jnp.int32(0))
    flat = jnp.pad(r.reshape(-1).astype(jnp.int32), (0, n_pad - n))
    u3 = _zigzag_jnp(flat).reshape(n_chunks, wpp, 32)
    dense, bits = _pack_planes_jnp(u3)
    words, n_words = _compact_jnp(dense, bits, wpp)
    return words, bits, n_words


@functools.partial(jax.jit, static_argnames=("shape", "chunk"))
def unpack_codes_jnp(words: jnp.ndarray, bits: jnp.ndarray,
                     shape: Tuple[int, ...], chunk: int = CHUNK
                     ) -> jnp.ndarray:
    """Inverse of ``pack_codes_jnp``: the int32 code array of ``shape``
    from the packed stream (``words`` may be exactly ``n_words`` long)."""
    n = 1
    for s in shape:
        n *= int(s)
    n_chunks, _, wpp = _chunk_layout(n, chunk)
    if n_chunks == 0:
        return jnp.zeros(shape, jnp.int32)
    dense = _expand_jnp(words.astype(jnp.uint32), bits.astype(jnp.int32),
                        wpp)
    u3 = _unpack_planes_jnp(dense, wpp)
    return _unzigzag_jnp(u3.reshape(-1))[:n].reshape(shape)


# ---------------------------------------------------------------------------
# pallas kernels (production path; one grid program per chunk)
# ---------------------------------------------------------------------------

#: chunks per pallas grid program (a multiple of 8: the bit-width column
#: block is (CHUNKS_PER_BLOCK, 1))
CHUNKS_PER_BLOCK = 64


def _block_chunks(n_chunks: int) -> int:
    """Chunks per grid program: ``CHUNKS_PER_BLOCK``, or ``n_chunks``
    rounded up to a multiple of 8 for short streams."""
    return min(CHUNKS_PER_BLOCK, -(-n_chunks // 8) * 8)


def _pack_kernel(u_ref, dense_ref, bits_ref):
    """One block of chunks, each a (32, wpp) int32 tile holding code
    ``m*32 + t`` at [t, m]: plane k word m gathers bit k of the chunk's
    codes m*32..m*32+31, a sum over the tile's sublanes (the bits are
    disjoint, so the int32 sum is their OR). The bit width is one past
    the highest plane with a set bit."""
    u = u_ref[...]
    G = u.shape[0]
    t = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    bits = jnp.zeros((G, 1), jnp.int32)
    for k in range(32):
        plane = jnp.sum(((u >> k) & 1) << t, axis=1)
        dense_ref[:, k, :] = plane
        nz = jnp.max(jnp.where(plane != 0, 1, 0), axis=1, keepdims=True)
        bits = jnp.maximum(bits, nz * (k + 1))
    bits_ref[...] = bits


def _unpack_kernel(dense_ref, u_ref):
    """Inverse transpose of ``_pack_kernel``: code (t, m) of a chunk is
    the sum over planes k of bit t of word (k, m), shifted to bit k."""
    d = dense_ref[...]
    k = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    for t in range(32):
        u_ref[:, t, :] = jnp.sum(((d >> t) & 1) << k, axis=1)


def _to_tiles(flat_u32: jnp.ndarray, n_chunks: int, n_pad_chunks: int,
              wpp: int) -> jnp.ndarray:
    """Zigzagged codes -> (n_pad_chunks, 32, wpp) int32 tiles, code
    ``m*32 + t`` of each chunk at [t, m]; padding chunks are zero."""
    u = jax.lax.bitcast_convert_type(flat_u32, jnp.int32)
    u = u.reshape(n_chunks, wpp, 32).transpose(0, 2, 1)
    return jnp.pad(u, ((0, n_pad_chunks - n_chunks), (0, 0), (0, 0)))


def pack_codes_pallas(r: jnp.ndarray, chunk: int = CHUNK, *,
                      interpret: Optional[bool] = None):
    """``pack_codes_jnp`` with the per-chunk plane transpose running as
    a Pallas kernel (grid over blocks of ``CHUNKS_PER_BLOCK`` chunks,
    each chunk a (32, chunk/32) tile, so every block is the full extent
    of its last two dimensions). The offset prefix scan and the
    compaction scatter stay XLA-level around the kernel. Bitwise
    identical to the jnp and host codecs. The whole composition runs
    jitted so its scalar constants bake in at trace time (eager
    execution would ship them per call — an implicit transfer under
    ``debug.no_transfers()``)."""
    if interpret is None:
        interpret = default_interpret()
    return _pack_codes_pallas_jit(r, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _pack_codes_pallas_jit(r: jnp.ndarray, chunk: int, interpret: bool):
    n = r.size
    n_chunks, n_pad, wpp = _chunk_layout(n, chunk)
    if n_chunks == 0:
        return (jnp.zeros((0,), jnp.uint32), jnp.zeros((0,), jnp.int32),
                jnp.int32(0))
    flat = jnp.pad(r.reshape(-1).astype(jnp.int32), (0, n_pad - n))
    G = _block_chunks(n_chunks)
    n_blk = -(-n_chunks // G)
    tiles = _to_tiles(_zigzag_jnp(flat), n_chunks, n_blk * G, wpp)
    dense, bits = pl.pallas_call(
        _pack_kernel,
        grid=(n_blk,),
        in_specs=[pl.BlockSpec((G, 32, wpp), lambda c: (c, 0, 0))],
        out_specs=[pl.BlockSpec((G, 32, wpp), lambda c: (c, 0, 0)),
                   pl.BlockSpec((G, 1), lambda c: (c, 0))],
        out_shape=[jax.ShapeDtypeStruct(tiles.shape, jnp.int32),
                   jax.ShapeDtypeStruct((n_blk * G, 1), jnp.int32)],
        interpret=interpret,
    )(tiles)
    dense = jax.lax.bitcast_convert_type(
        dense[:n_chunks].reshape(n_chunks, 32 * wpp), jnp.uint32)
    bits = bits[:n_chunks, 0]
    words, n_words = _compact_jnp(dense, bits, wpp)
    return words, bits, n_words


def unpack_codes_pallas(words: jnp.ndarray, bits: jnp.ndarray,
                        shape: Tuple[int, ...], chunk: int = CHUNK, *,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Inverse of ``pack_codes_pallas``: XLA-level expand gather, then
    the per-chunk plane transpose back to codes as a Pallas kernel.
    Jitted end to end (see ``pack_codes_pallas``)."""
    if interpret is None:
        interpret = default_interpret()
    return _unpack_codes_pallas_jit(words, bits, shape=tuple(shape),
                                    chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("shape", "chunk", "interpret"))
def _unpack_codes_pallas_jit(words: jnp.ndarray, bits: jnp.ndarray,
                             shape: Tuple[int, ...], chunk: int,
                             interpret: bool) -> jnp.ndarray:
    n = 1
    for s in shape:
        n *= int(s)
    n_chunks, _, wpp = _chunk_layout(n, chunk)
    if n_chunks == 0:
        return jnp.zeros(shape, jnp.int32)
    dense = _expand_jnp(words.astype(jnp.uint32), bits.astype(jnp.int32),
                        wpp)
    G = _block_chunks(n_chunks)
    n_blk = -(-n_chunks // G)
    d3 = jax.lax.bitcast_convert_type(dense, jnp.int32).reshape(
        n_chunks, 32, wpp)
    d3 = jnp.pad(d3, ((0, n_blk * G - n_chunks), (0, 0), (0, 0)))
    u = pl.pallas_call(
        _unpack_kernel,
        grid=(n_blk,),
        in_specs=[pl.BlockSpec((G, 32, wpp), lambda c: (c, 0, 0))],
        out_specs=pl.BlockSpec((G, 32, wpp), lambda c: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(d3.shape, jnp.int32),
        interpret=interpret,
    )(d3)
    u = jax.lax.bitcast_convert_type(
        u[:n_chunks].transpose(0, 2, 1).reshape(-1), jnp.uint32)
    return _unzigzag_jnp(u)[:n].reshape(shape)


# ---------------------------------------------------------------------------
# numpy mirror (byte-level blob codec + conformance oracle)
# ---------------------------------------------------------------------------

def _zigzag_np(r: np.ndarray) -> np.ndarray:
    v = np.asarray(r, np.int64)
    return (((v << 1) ^ (v >> 31)) & 0xFFFFFFFF).astype(np.uint32)


def _unzigzag_np(u: np.ndarray) -> np.ndarray:
    u = np.asarray(u, np.uint64)
    v = (u >> np.uint64(1)).astype(np.int64) ^ -(u & np.uint64(1)).astype(
        np.int64)
    return v.astype(np.int32)


def _bits_np(maxu: np.ndarray) -> np.ndarray:
    """Per-chunk bit widths: bit_length of the max zigzagged magnitude
    (exact — no float log2)."""
    thresholds = (np.uint64(1) << np.arange(32, dtype=np.uint64))
    return np.sum(maxu.astype(np.uint64)[:, None] >= thresholds[None, :],
                  axis=1).astype(np.int32)


def pack_codes_host(r: np.ndarray, chunk: int = CHUNK
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """numpy twin of ``pack_codes_jnp``: ``(words, bits)`` with
    ``words`` already sliced to the true stream length. Backs the
    host-path blob codec and the device-codec conformance oracle —
    int32 range required (the device codes' own domain)."""
    flat = np.asarray(r).reshape(-1)
    if flat.size and not (np.all(flat >= np.iinfo(np.int32).min)
                          and np.all(flat <= np.iinfo(np.int32).max)):
        raise ValueError("device-pack serves int32 residual codes only")
    n = flat.size
    n_chunks, n_pad, wpp = _chunk_layout(n, chunk)
    if n_chunks == 0:
        return np.zeros(0, np.uint32), np.zeros(0, np.int32)
    u3 = np.zeros(n_pad, np.uint32)
    u3[:n] = _zigzag_np(flat)
    u3 = u3.reshape(n_chunks, wpp, 32)
    bits = _bits_np(u3.max(axis=(1, 2)))
    t = np.arange(32, dtype=np.uint32)
    dense = np.empty((n_chunks, 32, wpp), np.uint32)
    for k in range(32):
        dense[:, k, :] = np.sum(
            ((u3 >> np.uint32(k)) & np.uint32(1)) << t, axis=2,
            dtype=np.uint32)
    keep = np.arange(32)[None, :] < bits[:, None]          # (n_chunks, 32)
    return dense[keep].reshape(-1), bits


def unpack_codes_host(words: np.ndarray, bits: np.ndarray, n: int,
                      chunk: int = CHUNK) -> np.ndarray:
    """Inverse of ``pack_codes_host``: the flat int32 code array of
    length ``n``. Validates the stream length against the bit widths
    (truncated or over-long streams are hard errors, never a silent
    short decode)."""
    bits = np.asarray(bits, np.int64)
    words = np.asarray(words, np.uint32)
    n_chunks, _, wpp = _chunk_layout(n, chunk)
    if bits.size != n_chunks:
        raise ValueError(
            f"bit-width table has {bits.size} chunks, expected {n_chunks} "
            f"for {n} codes at chunk={chunk}")
    if np.any(bits < 0) or np.any(bits > 32):
        raise ValueError("chunk bit widths must lie in [0, 32]")
    expect = int(np.sum(bits)) * wpp
    if words.size != expect:
        raise ValueError(
            f"packed stream has {words.size} words, expected {expect} "
            "(truncated or over-long device-pack blob)")
    if n_chunks == 0:
        return np.zeros(0, np.int32)
    dense = np.zeros((n_chunks, 32, wpp), np.uint32)
    keep = np.arange(32)[None, :] < bits[:, None]
    dense[keep] = words.reshape(-1, wpp)
    t = np.arange(32, dtype=np.uint32)
    u3 = np.zeros((n_chunks, wpp, 32), np.uint32)
    for k in range(32):
        u3 |= ((dense[:, k, :, None] >> t) & np.uint32(1)) << np.uint32(k)
    return _unzigzag_np(u3.reshape(-1)[:n])
