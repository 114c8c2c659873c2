"""The plain reference that decides ``correct``.

It imports nothing of the program. It reads an artifact's bytes by their
documented layout, rebuilds the field the artifact says, and judges that
field against the original by the two guarantees the configuration
states:

* every vertex keeps its Morse-Smale label pair: the maximum its
  steepest ascending line reaches and the minimum its steepest
  descending line reaches, on the Freudenthal triangulation of the grid
  (6 neighbours in 2D, 14 in 3D), with ties broken by the linear index
  (simulation of simplicity);
* every value stays within ``xi`` of the original.

Decoding runs in numpy on the host (DEFLATE streams, varints, integer
prefix sums, one float32 multiply and one float32 add per value: the
layout's own arithmetic). Labels run in plain ``jax.numpy`` on the
device: a loop over the stencil for each vertex's steepest neighbour,
then pointer doubling until every pointer reaches its root. The
original's labels are computed in full; a candidate keeps them all
exactly when each vertex's steepest neighbour in the candidate shares
its label in the original and the candidate's extrema are the
original's (``_violations``), which needs no second pointer doubling.

Layouts read here: the szlike base payload ``SZJ2`` (DEFLATE'd int8
residual codes with an int64 escape list, Lorenzo residuals of
``round(f / step)``) and the edit stream ``MSE1`` (varint index deltas
and float values, each DEFLATE'd). Any other magic raises, and a raise
counts as a wrong answer.
"""
from __future__ import annotations

import functools
import struct
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Freudenthal stencils: offsets along the grid's axes, in +/- pairs
STENCIL_2D = ((0, 1), (0, -1), (1, 0), (-1, 0), (1, 1), (-1, -1))
STENCIL_3D = ((0, 0, 1), (0, 0, -1), (0, 1, 0), (0, -1, 0),
              (1, 0, 0), (-1, 0, 0), (0, 1, 1), (0, -1, -1),
              (1, 0, 1), (-1, 0, -1), (1, 1, 0), (-1, -1, 0),
              (1, 1, 1), (-1, -1, -1))

_SZ_HEAD = "<4sBBdQ"
_EDIT_HEAD = "<4sBQQQ"


class BadArtifact(ValueError):
    """The bytes do not hold what their layout promises."""


# ---------------------------------------------------------------------------
# decoding
# ---------------------------------------------------------------------------

def _inflate(buf: memoryview, off: int) -> Tuple[bytes, int]:
    (n,) = struct.unpack_from("<Q", buf, off)
    off += 8
    if off + n > len(buf):
        raise BadArtifact("truncated residual stream")
    return zlib.decompress(buf[off:off + n]), off + n


def decode_base(payload: bytes) -> np.ndarray:
    """The field an ``SZJ2`` payload encodes (before edits)."""
    buf = memoryview(payload)
    magic, ndim, dt, step, size = struct.unpack_from(_SZ_HEAD, buf, 0)
    if magic != b"SZJ2":
        raise BadArtifact(f"base payload magic {magic!r} is not SZJ2")
    if dt != 0:
        raise BadArtifact("only float32 payloads are read here")
    off = struct.calcsize(_SZ_HEAD)
    shape = struct.unpack_from(f"<{ndim}Q", buf, off)
    off += 8 * ndim
    if int(np.prod(shape)) != size:
        raise BadArtifact("payload shape and size disagree")
    main, off = _inflate(buf, off)
    esc_i, off = _inflate(buf, off)
    esc_v, off = _inflate(buf, off)
    if off != len(buf):
        raise BadArtifact("trailing bytes after the residual streams")
    codes = np.frombuffer(main, np.int8).astype(np.int64)
    if codes.size != size:
        raise BadArtifact(f"{codes.size} residual codes for {size} values")
    ei = np.frombuffer(esc_i, np.int64)
    ev = np.frombuffer(esc_v, np.int64)
    if ei.size != ev.size or np.any(codes[ei] != -128):
        raise BadArtifact("escape list does not match its markers")
    codes[ei] = ev
    return _prefix_sums(codes.reshape(shape)).astype(np.float32) \
        * np.float32(step)


def _prefix_sums(q: np.ndarray) -> np.ndarray:
    """Inclusive prefix sums along every axis, in place (int64): the
    inverse of the Lorenzo residual. The outer axes run slab by slab so
    that every add streams contiguous memory."""
    np.cumsum(q, axis=-1, out=q)
    if q.ndim == 3:
        for z in range(q.shape[0]):
            np.cumsum(q[z], axis=0, out=q[z])
    for z in range(1, q.shape[0]):
        q[z] += q[z - 1]
    return q


def _varints(raw: bytes, count: int) -> np.ndarray:
    data = np.frombuffer(raw, np.uint8)
    last = (data & 0x80) == 0
    if int(last.sum()) != count or (count and not last[-1]):
        raise BadArtifact("varint stream does not hold its count")
    if count == 0:
        return np.zeros(0, np.int64)
    first = np.concatenate([[True], last[:-1]])
    starts = np.flatnonzero(first)
    owner = np.cumsum(first) - 1
    shift = (np.arange(data.size) - starts[owner]).astype(np.uint64) * 7
    groups = (data & 0x7F).astype(np.uint64) << shift
    return np.add.reduceat(groups, starts).astype(np.int64)


def decode_edits(blob: bytes, size: int) -> Tuple[np.ndarray, np.ndarray]:
    """(indices, float32 deltas) of an ``MSE1`` edit stream."""
    magic, dt, n, lk, lv = struct.unpack_from(_EDIT_HEAD, blob, 0)
    if magic != b"MSE1":
        raise BadArtifact(f"edit magic {magic!r} is not MSE1")
    off = struct.calcsize(_EDIT_HEAD)
    if off + lk + lv != len(blob):
        raise BadArtifact("edit stream lengths disagree with its size")
    keys = zlib.decompress(blob[off:off + lk])
    vals = zlib.decompress(blob[off + lk:off + lk + lv])
    idx = np.cumsum(_varints(keys, n), dtype=np.int64)
    if dt == 0:
        val = np.frombuffer(vals, np.float32)
    elif dt == 1:
        val = (np.frombuffer(vals, np.uint16).astype(np.uint32) << 16
               ).view(np.float32)
    else:
        raise BadArtifact(f"edit value type {dt} is not float32 or bfloat16")
    if val.size != n:
        raise BadArtifact(f"{val.size} edit values for {n} indices")
    if n and (idx[0] < 0 or idx[-1] >= size or np.any(np.diff(idx) <= 0)):
        raise BadArtifact("edit indices out of range or not increasing")
    return idx, val


def decode(base_payload: bytes, edit_payload: bytes) -> np.ndarray:
    """The field an artifact says: base field plus its edits."""
    g = decode_base(base_payload)
    idx, val = decode_edits(edit_payload, g.size)
    flat = g.reshape(-1)
    flat[idx] = flat[idx] + val
    return g


# ---------------------------------------------------------------------------
# labels
# ---------------------------------------------------------------------------

def _steepest(f: jnp.ndarray, ascending: bool) -> jnp.ndarray:
    """Flat index of each vertex's steepest neighbour (itself at an
    extremum): the largest (value, index) pair when ascending, the
    smallest when descending."""
    stencil = STENCIL_3D if f.ndim == 3 else STENCIL_2D
    offs = jnp.asarray(stencil, jnp.int32)
    strides = np.cumprod((1,) + f.shape[:0:-1])[::-1].astype(np.int32)
    fill = -jnp.inf if ascending else jnp.inf
    fp = jnp.pad(f, 1, constant_values=fill)
    lin = jax.lax.broadcasted_iota(jnp.int32, f.shape, 0) * strides[0]
    for ax in range(1, f.ndim):
        lin = lin + jax.lax.broadcasted_iota(jnp.int32, f.shape, ax) \
            * strides[ax]

    def body(k, best):
        bv, bi = best
        off = offs[k]
        nv = jax.lax.dynamic_slice(fp, tuple(off + 1), f.shape)
        ni = lin + jnp.sum(off * jnp.asarray(strides))
        if ascending:
            win = (nv > bv) | ((nv == bv) & (ni > bi))
        else:
            win = (nv < bv) | ((nv == bv) & (ni < bi))
        return jnp.where(win, nv, bv), jnp.where(win, ni, bi)

    _, best = jax.lax.fori_loop(0, len(stencil), body, (f, lin))
    return best.reshape(-1)


def _roots(p: jnp.ndarray) -> jnp.ndarray:
    """Follow every pointer to its root by doubling."""
    def cond(state):
        return state[1]

    def body(state):
        q = jnp.take(state[0], state[0])
        return q, jnp.any(q != state[0])

    return jax.lax.while_loop(cond, body, (p, jnp.bool_(True)))[0]


@jax.jit
def labels(f: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(maximum label, minimum label) of every vertex, flat."""
    return _roots(_steepest(f, True)), _roots(_steepest(f, False))


@jax.jit
def _violations(mf, nf, g) -> jnp.ndarray:
    """Vertices of ``g`` whose steepest neighbour lies outside their label
    region of f, or that are extrema of ``g`` and not of f. Zero exactly
    when every label pair of ``g`` equals f's: following g's steepest
    line from any vertex then never leaves its region of f and ends at an
    extremum of f, which is its own label (and conversely)."""
    lin = jnp.arange(g.size, dtype=jnp.int32)
    bad = jnp.zeros(g.size, bool)
    for lab, ascending in ((mf, True), (nf, False)):
        nxt = _steepest(g, ascending)
        bad = bad | (jnp.take(lab, nxt) != lab) | ((nxt == lin) & (lab != lin))
    return jnp.sum(bad, dtype=jnp.int32)


def _max_abs_err(f: np.ndarray, g: np.ndarray, chunk: int = 1 << 24) -> float:
    a, b = f.reshape(-1), g.reshape(-1)
    worst = 0.0
    for s in range(0, a.size, chunk):
        d = np.abs(a[s:s + chunk].astype(np.float64)
                   - b[s:s + chunk].astype(np.float64))
        m = float(np.max(d)) if d.size else 0.0
        if not m <= worst:          # NaN propagates as a failure
            worst = m
    return worst


class Original:
    """One original field with its labels, computed once and compared
    against any number of candidate fields."""

    def __init__(self, f: np.ndarray, xi: float):
        self.f = f
        self.xi = float(xi)
        self._labels = labels(jnp.asarray(f))

    def compare(self, g: np.ndarray) -> Dict[str, float]:
        """``label_violations``: vertices at which g breaks a label pair
        of f (``_violations``; 0 exactly when all are kept);
        ``err_over_xi``: max |f - g| / xi (NaN when g holds NaN)."""
        if g.shape != self.f.shape:
            return dict(label_violations=float(self.f.size),
                        err_over_xi=float("inf"))
        n = int(_violations(*self._labels, jnp.asarray(g, jnp.float32)))
        return dict(label_violations=float(n),
                    err_over_xi=_max_abs_err(self.f, g) / self.xi)


def bitwise_mismatch(a: np.ndarray, b: np.ndarray) -> int:
    """Values whose float32 bits differ (shapes must agree)."""
    if a.shape != b.shape:
        return int(max(a.size, b.size))
    return int(np.count_nonzero(a.reshape(-1).view(np.uint32)
                                != b.reshape(-1).view(np.uint32)))


def to_bfloat16(f: np.ndarray) -> np.ndarray:
    """``f`` rounded to bfloat16 (nearest, ties to even) and widened
    back: the control, a compressor one precision below the stated one."""
    return np.asarray(jnp.asarray(f).astype(jnp.bfloat16).astype(jnp.float32))
