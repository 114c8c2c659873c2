"""Structured-grid PL topology primitives (Freudenthal triangulation).

The paper operates on triangular/tetrahedral meshes; every dataset it
evaluates is a structured grid, for which the Freudenthal triangulation
yields fixed neighbor stencils:

  * 2D: 6-neighborhood  (4 axis + the (+1,+1)/(-1,-1) diagonal)
  * 3D: 14-neighborhood (6 axis + 8 diagonal offsets along the main diagonal)

All comparisons use Simulation-of-Simplicity (SoS) total ordering
``(value, linear_index)`` so non-Morse (tied) inputs are handled exactly as
in the paper (Edelsbrunner & Muecke).

Everything here is expressed as dense shift-based stencils (pad + slice),
which XLA fuses well and which map 1:1 onto the Pallas TPU kernels in
``repro.kernels``.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Freudenthal stencils. Offsets come in +/- pairs: code(2k+1) = -code(2k).
OFFSETS_2D: Tuple[Tuple[int, ...], ...] = (
    (0, 1), (0, -1),
    (1, 0), (-1, 0),
    (1, 1), (-1, -1),
)
OFFSETS_3D: Tuple[Tuple[int, ...], ...] = (
    (0, 0, 1), (0, 0, -1),
    (0, 1, 0), (0, -1, 0),
    (1, 0, 0), (-1, 0, 0),
    (0, 1, 1), (0, -1, -1),
    (1, 0, 1), (-1, 0, -1),
    (1, 1, 0), (-1, -1, 0),
    (1, 1, 1), (-1, -1, -1),
)


def offsets_for(ndim: int) -> Tuple[Tuple[int, ...], ...]:
    """The paper's neighbor stencil offsets: 8-neighborhood in 2D,
    14-neighborhood (6 face + 8 body diagonal) in 3D."""
    if ndim == 2:
        return OFFSETS_2D
    if ndim == 3:
        return OFFSETS_3D
    raise ValueError(f"MSz supports 2D/3D piecewise-linear fields, got ndim={ndim}")


def n_neighbors(ndim: int) -> int:
    """Stencil size: 8 in 2D, 14 in 3D."""
    return len(offsets_for(ndim))


def self_code(ndim: int) -> int:
    """Direction code meaning 'self' (the vertex is an extremum)."""
    return n_neighbors(ndim)


def shift(x: jnp.ndarray, off: Sequence[int], fill) -> jnp.ndarray:
    """y[v] = x[v + off], with ``fill`` outside the domain."""
    pads = [(max(0, -o), max(0, o)) for o in off]
    xp = jnp.pad(x, pads, constant_values=fill)
    sl = tuple(slice(max(0, o), max(0, o) + s) for o, s in zip(off, x.shape))
    return xp[sl]


def linear_index(shape: Sequence[int]) -> jnp.ndarray:
    """Row-major flat vertex ids of a grid, shaped like the grid (the
    SoS tie-break key: lower id wins ties)."""
    return jnp.arange(int(np.prod(shape)), dtype=jnp.int32).reshape(shape)


def _sos_argbest(vals: jnp.ndarray, idxs: jnp.ndarray, *, ascending: bool):
    """Slot of the SoS-lexicographic best along axis 0 of stacked
    (values, linear indices): max (v, i) when ascending, min otherwise.

    Three small reductions instead of a chained compare-and-select scan:
    the scan's carried values feed every comparison of every later step,
    and XLA:CPU's elemental emitter re-emits each operand expression per
    use, making one fused 14-step scan kernel exponential (~4x compile
    time per stencil neighbor, >10^5 s for the full Freudenthal stencil).
    Reductions are emitted as loops, keeping codegen linear; results are
    bitwise identical to the scan.

    The winning slot is an int32 min over slot numbers, not an argmax
    over a bool stack: Mosaic lowers argmax for float32 only. A slot set
    with no winner (NaN candidates) yields slot 0, as argmax did.
    """
    if ascending:
        v_best = jnp.max(vals, axis=0)
        i_fill = jnp.int32(np.iinfo(np.int32).min)
        i_best = jnp.max(jnp.where(vals == v_best, idxs, i_fill), axis=0)
    else:
        v_best = jnp.min(vals, axis=0)
        i_fill = jnp.int32(np.iinfo(np.int32).max)
        i_best = jnp.min(jnp.where(vals == v_best, idxs, i_fill), axis=0)
    n = vals.shape[0]
    win = (vals == v_best) & (idxs == i_best)
    slot_ids = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)
    slot = jnp.min(jnp.where(win, slot_ids, jnp.int32(n)), axis=0)
    return jnp.where(slot == n, jnp.int32(0), slot)


#: voxels per z-chunk of a 3D ``steepest_dirs``: the stacked stencil
#: candidates take 15 x 8 bytes per voxel, so one chunk's stack stays
#: near 0.5 GiB instead of growing with the field (a 512^3 field's whole
#: stack would need 23 GB)
_CHUNK_VOXELS = 1 << 22


def _fills(dtype, ascending: bool):
    """(value, linear index) of an off-domain neighbor: it never wins."""
    if ascending:
        return jnp.asarray(-jnp.inf, dtype), jnp.int32(-1)
    return jnp.asarray(jnp.inf, dtype), jnp.int32(np.iinfo(np.int32).max)


def _codes(f: jnp.ndarray, lin: jnp.ndarray, *, ascending: bool
           ) -> jnp.ndarray:
    """Steepest-neighbor codes of every vertex of ``f`` (linear indices
    ``lin``), with off-array neighbors filled as off-domain ones."""
    offs = offsets_for(f.ndim)
    fill_v, fill_i = _fills(f.dtype, ascending)
    vals = jnp.stack([f] + [shift(f, o, fill_v) for o in offs])
    idxs = jnp.stack([lin] + [shift(lin, o, fill_i) for o in offs])
    slot = _sos_argbest(vals, idxs, ascending=ascending)
    return jnp.where(slot == 0, jnp.int32(self_code(f.ndim)), slot - 1)


def _codes_chunked(f: jnp.ndarray, lin: jnp.ndarray, chunk: int, *,
                   ascending: bool) -> jnp.ndarray:
    """``_codes`` over z-chunks of ``chunk`` slabs, one after another.
    Each chunk sees a 1-slab window margin; margins past the domain
    carry the off-domain fills, so every kept slab's candidates — and
    so its codes — equal the whole-field computation's bit for bit."""
    n = f.shape[0]
    n_chunks = -(-n // chunk)
    pad = ((1, 1 + n_chunks * chunk - n),) + ((0, 0),) * (f.ndim - 1)
    fill_v, fill_i = _fills(f.dtype, ascending)
    fp = jnp.pad(f, pad, constant_values=fill_v)
    lp = jnp.pad(lin, pad, constant_values=fill_i)

    def one(c):
        z0 = c * chunk
        w = jax.lax.dynamic_slice_in_dim(fp, z0, chunk + 2)
        wl = jax.lax.dynamic_slice_in_dim(lp, z0, chunk + 2)
        return _codes(w, wl, ascending=ascending)[1:-1]

    out = jax.lax.map(one, jnp.arange(n_chunks, dtype=jnp.int32))
    return out.reshape((n_chunks * chunk,) + f.shape[1:])[:n]


@functools.partial(jax.jit, static_argnames=())
def steepest_dirs(f: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused 'update directions' + 'classify extrema' stencil.

    Returns ``(up_code, dn_code)`` int32 arrays of f's shape. ``up_code[v]``
    is the stencil code of the steepest SoS-ascending neighbor of ``v``
    (the first edge of v's ascending integral line), or ``self_code(ndim)``
    when ``v`` is a maximum. Symmetrically for ``dn_code`` / minima.

    This is the paper's dominant component ('updating directions', ~80% of
    CPU time, Table 1) fused with its 'find critical points' pass. Slot 0
    of the stacked candidates is the vertex itself, so slot k+1 is stencil
    code k and slot 0 winning means 'extremum'. 3D fields larger than
    ``_CHUNK_VOXELS`` run in z-chunks (``_codes_chunked``), which bounds
    the candidate stack's memory; the codes are the same.
    """
    lin = linear_index(f.shape)
    plane = int(np.prod(f.shape[1:]))
    chunk = max(1, _CHUNK_VOXELS // plane)
    if f.ndim != 3 or f.shape[0] <= chunk:
        return _codes(f, lin, ascending=True), _codes(f, lin, ascending=False)
    return (_codes_chunked(f, lin, chunk, ascending=True),
            _codes_chunked(f, lin, chunk, ascending=False))


def gather_dir(x: jnp.ndarray, code: jnp.ndarray) -> jnp.ndarray:
    """y[v] = x[v + offset(code[v])]; y[v] = x[v] where code==self."""
    offs = offsets_for(x.ndim)
    out = x
    zero = jnp.zeros((), x.dtype)
    for k, off in enumerate(offs):
        # fill value irrelevant — a valid code never points off-domain.
        out = jnp.where(code == k, shift(x, off, zero), out)
    return out


def dir_to_pointer(code: jnp.ndarray) -> jnp.ndarray:
    """Direction codes -> flattened next-vertex pointers (self at extrema)."""
    lin = linear_index(code.shape)
    nxt = gather_dir(lin, code)
    return nxt.reshape(-1)


def is_extremum(code: jnp.ndarray) -> jnp.ndarray:
    return code == self_code(code.ndim)
