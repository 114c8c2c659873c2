"""Pallas TPU kernel: fused 'update directions' + 'find false critical
points' (the paper's two dominant components, Table 1) for 2D and 3D
fields.

TPU mapping (``slab_layout``): a grid of programs along the leading
axis, each seeing its own block plus a 1-vertex halo on both sides of
it — the TPU-native replacement for the paper's per-thread vertex loop.

* 3D (Z, Y, X): one program per z-slab; the halo is the slabs s-1 and
  s+1, read through overlapping (1, Y, X) BlockSpecs with clamped index
  maps. Every Freudenthal neighbor is a static slab select in
  {-1, 0, +1} plus a static in-plane shift.
* 2D (Y, X): one program per block of ``block_rows`` rows (a multiple
  of 8, so blocks meet Mosaic's (8, 128) tiling rule); the halo rows
  above and below come from 8-row blocks of the same array. A 2D field
  is a single-slab 3D field whose plane carries a row halo.

The whole stencil is vector ops on VMEM-resident planes; SoS
tie-breaking uses arithmetic linear indices (no index arrays are
loaded).

Tiled execution (pMSz-style block decomposition, see DESIGN.md §3/§9):
``slab_lo`` / ``n_slabs_total`` place a tile of the leading axis inside
a larger field, and ``row_lo``/``col_lo`` with ``n_rows_total``/
``n_cols_total`` place the tile's *plane* inside a larger global plane
(the 2D/3D block decomposition of the sharded backend). Domain-boundary
handling and SoS linear indices then use *global* coordinates, so
outputs on vertices whose 1-vertex halo lies inside the tile are bitwise
identical to an untiled run; the tile drivers (core.backend.PallasBackend
z-tiles, distributed.shardfix blocks) keep a halo margin and discard the
rest. Vertices past the array end in a partial last 2D block read
padding and are never written back.

Outputs per vertex: steepest ascending/descending direction codes of g,
and the three fix-source masks (self_edit / demote / promote) consumed by
the fix kernel. VMEM: every grid step holds 12 input and 5 output blocks,
double-buffered — 32 blocks, 32 MiB for a 512x512 f32 plane — so each
``pallas_call`` states its scoped VMEM limit (``vmem_params``); a plane
too large for the chip's VMEM raises instead of falling back.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.grid import OFFSETS_2D, OFFSETS_3D, _sos_argbest


# platforms with a native Pallas lowering (Mosaic on TPU, Triton on GPU);
# everything else — notably XLA:CPU — must run the kernels interpreted
_LOWERED_PLATFORMS = ("tpu", "gpu", "cuda", "rocm")

#: VMEM a kernel may request: v5e has 128 MiB per TensorCore, and the
#: rest stays free for Mosaic's internal scratch
VMEM_REQUEST_CAP = 100 * 2**20
#: Mosaic's default scoped VMEM limit, the floor of every request
_VMEM_DEFAULT = 16 * 2**20
#: bytes of one 2D row block: about one 512x512 f32 plane, the 3D
#: kernels' per-slab block at SDRBench Nyx size
_ROW_BLOCK_BYTES = 2**20
#: rows of a 2D halo block (the sublane tile of 32-bit data)
_HALO_ROWS = 8
#: plane-sized temporaries Mosaic keeps in VMEM for each kernel body,
#: by (kernel, ndim): measured from the v5e compiler's scoped
#: allocation at 256^2 and 512^2 planes and at 1800x3600 / 4096^2 2D
#: fields, rounded up
TEMP_PLANES = {("extrema", 3): 32, ("extrema", 2): 16,
               ("fixpass", 3): 52, ("fixpass", 2): 24,
               ("lorenzo", 3): 8, ("lorenzo", 2): 4}


def default_interpret() -> bool:
    """Whether Pallas kernels should run in interpret mode by default.

    Auto-detects the platform: TPUs lower through Mosaic and GPUs through
    Triton, so both take the compiled path; every other backend (XLA:CPU
    in particular) has no Pallas lowering and interprets. The
    ``MSZ_PALLAS_INTERPRET`` environment variable overrides the detection
    in both directions (``1``/``true``/``yes``/``on`` forces interpret
    mode, ``0``/``false``/``no``/``off`` forces the lowered path) — the
    escape hatch for debugging a kernel on an accelerator, or for
    asserting lowered-vs-interpret bitwise identity in tests. Every
    kernel entry point (extrema, fix pass, Lorenzo) and every backend
    with ``interpret=None`` routes through this policy.
    """
    env = os.environ.get("MSZ_PALLAS_INTERPRET", "").strip().lower()
    if env in ("1", "true", "yes", "on"):
        return True
    if env in ("0", "false", "no", "off"):
        return False
    if env:
        raise ValueError(
            f"MSZ_PALLAS_INTERPRET={env!r} not understood; use one of "
            "1/true/yes/on (interpret) or 0/false/no/off (lowered)")
    return jax.default_backend() not in _LOWERED_PLATFORMS


def slab_offsets(ndim: int) -> Tuple[Tuple[int, int, int], ...]:
    """Freudenthal offsets as (slab_delta, dy, dx) triples.

    3D fields decompose over z-slabs of plane shape (Y, X); a 2D field is
    one slab whose plane is the row block itself, so its slab delta is
    always 0 and its dy reaches into the row halo.
    """
    if ndim == 3:
        return tuple(OFFSETS_3D)
    if ndim == 2:
        return tuple((0, dy, dx) for (dy, dx) in OFFSETS_2D)
    raise ValueError(f"slab kernels support 2D/3D fields, got ndim={ndim}")


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) block laid out in (8, 128) tiles."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * itemsize


def block_rows(n_rows: int, n_cols: int) -> int:
    """Rows per program of a 2D field: the multiple of 8 whose (rows,
    n_cols) block holds about ``_ROW_BLOCK_BYTES`` of 32-bit data, or
    every row when the field is shorter than that."""
    r = _ROW_BLOCK_BYTES // _tile_bytes(8, n_cols, 4) * 8
    r = max(r, _HALO_ROWS)
    return n_rows if n_rows <= r else r


class SlabLayout(NamedTuple):
    """Grid and blocks of a slab-decomposed field (see module docstring).

    ``P`` x ``X`` is the plane one program computes. ``halo`` maps a
    program to the blocks holding its lower neighbors, itself, and its
    upper neighbors; 2D halo blocks hold ``halo_rows`` rows."""
    ndim: int
    grid: int
    P: int
    X: int
    halo: Tuple[pl.BlockSpec, pl.BlockSpec, pl.BlockSpec]
    halo_rows: int

    @property
    def center(self) -> pl.BlockSpec:
        """A program's own block."""
        return self.halo[1]

    @property
    def S(self) -> int:
        """Leading-axis entries one program covers: a slab in 3D, its
        ``P`` rows in 2D."""
        return 1 if self.ndim == 3 else self.P

    def plane_bytes(self, itemsize: int) -> int:
        """VMEM bytes of one program's own block."""
        return _tile_bytes(self.P, self.X, itemsize)

    def edge_bytes(self, itemsize: int) -> int:
        """VMEM bytes of one of its two halo blocks."""
        if self.ndim == 3:
            return self.plane_bytes(itemsize)
        return _tile_bytes(self.halo_rows, self.X, itemsize)

    def block_bytes(self, itemsize: int, *, halo: int, center: int) -> int:
        """VMEM bytes of ``halo`` halo'd inputs (three blocks each) and
        ``center`` single-block operands of ``itemsize`` bytes."""
        return (halo * (self.plane_bytes(itemsize)
                        + 2 * self.edge_bytes(itemsize))
                + center * self.plane_bytes(itemsize))


def slab_layout(shape: Sequence[int]) -> SlabLayout:
    """The ``SlabLayout`` of a 2D/3D array of ``shape``.

    The halo blocks are clamped to the *local* array; clamping at a tile
    edge that is not a domain edge yields garbage the tile driver must
    discard (the kernels mask true domain edges themselves, in global
    coordinates).
    """
    if len(shape) == 3:
        n, P, X = shape
        halo = (
            pl.BlockSpec((1, P, X), lambda z: (jnp.maximum(z - 1, 0), 0, 0)),
            pl.BlockSpec((1, P, X), lambda z: (z, 0, 0)),
            pl.BlockSpec((1, P, X), lambda z: (jnp.minimum(z + 1, n - 1),
                                                0, 0)),
        )
        return SlabLayout(3, n, P, X, halo, 1)
    if len(shape) != 2:
        raise ValueError(f"slab kernels support 2D/3D, got shape {shape}")
    Y, X = shape
    R = block_rows(Y, X)
    H = min(_HALO_ROWS, Y)
    q = R // H                      # halo blocks per row block
    n_h = -(-Y // H)
    halo = (
        pl.BlockSpec((H, X), lambda i: (jnp.maximum(i * q - 1, 0), 0)),
        pl.BlockSpec((R, X), lambda i: (i, 0)),
        pl.BlockSpec((H, X), lambda i: (jnp.minimum((i + 1) * q, n_h - 1),
                                        0)),
    )
    return SlabLayout(2, -(-Y // R), R, X, halo, H)


def vmem_params(nbytes: int, temp_bytes: int, what: str
                ) -> pltpu.CompilerParams:
    """Mosaic parameters for a kernel whose grid step holds ``nbytes``
    of in/out blocks, double-buffered, and ``temp_bytes`` of kernel-body
    temporaries; the request carries a quarter of headroom, up to
    ``VMEM_REQUEST_CAP``. A plane too large for the chip raises — there
    is no fallback to another backend. Interpreted kernels have no VMEM
    and take no parameters."""
    need = 2 * nbytes + temp_bytes
    if need > VMEM_REQUEST_CAP:
        raise ValueError(
            f"{what}: one grid step needs {need / 2**20:.1f} MiB of VMEM "
            f"({nbytes / 2**20:.1f} MiB of blocks, double-buffered, plus "
            f"{temp_bytes / 2**20:.1f} MiB of temporaries), over the "
            f"{VMEM_REQUEST_CAP / 2**20:.0f} MiB a kernel may use; the "
            "field's plane is too large for the slab kernels")
    limit = min(max(need + need // 4, _VMEM_DEFAULT), VMEM_REQUEST_CAP)
    return pltpu.CompilerParams(vmem_limit_bytes=limit)


def program_coords(origin_c, lay: SlabLayout):
    """(z, yg, xg) of this program: its global slab index (0 in 2D) and
    the global row/column of every vertex of its plane."""
    pid = pl.program_id(0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (lay.P, lay.X), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (lay.P, lay.X), 1)
    if lay.ndim == 3:
        z = origin_c[0, 0] + pid
        yg = origin_c[0, 1] + rows
    else:
        z = jnp.int32(0)
        yg = origin_c[0, 0] + pid * lay.P + rows
    return z, yg, origin_c[0, 2] + cols


def load_window(refs, lay: SlabLayout):
    """The three refs of one halo'd input as (lower, own, upper) values:
    whole planes in 3D, the adjacent rows in 2D (an upper ref of None,
    for backward-only stencils, stays None)."""
    lo, c, hi = refs
    if lay.ndim == 3:
        return tuple(None if r is None else r[...].reshape(lay.P, lay.X)
                     for r in refs)
    H = lay.halo_rows
    return (lo[H - 1:H, :], c[...], None if hi is None else hi[0:1, :])


def neighbor(win, lay: SlabLayout, ds: int, dy: int, dx: int, fill):
    """out[v] = input[v + (ds, dy, dx)] over this program's plane, with
    ``fill`` past the local array in x (and in y for 3D; 2D rows past
    the block come from the halo rows of ``win``)."""
    if lay.ndim == 3:
        return _shift2d(win[ds + 1], dy, dx, fill)
    c = win[1]
    if dy:
        shifted = _shift2d(c, dy, 0, fill)
        edge_row = 0 if dy == -1 else lay.P - 1
        rows = jax.lax.broadcasted_iota(jnp.int32, c.shape, 0)
        halo_row = jnp.broadcast_to(win[0] if dy == -1 else win[2], c.shape)
        c = jnp.where(rows == edge_row, halo_row, shifted)
    return _shift2d(c, 0, dx, fill)


def _shift2d(a, dy: int, dx: int, fill):
    """Static in-plane shift: out[y,x] = a[y+dy, x+dx], `fill` off-edge."""
    Y, X = a.shape
    pads = [(max(0, -dy), max(0, dy)), (max(0, -dx), max(0, dx))]
    ap = jnp.pad(a, pads, constant_values=fill)
    return jax.lax.slice(ap, (max(0, dy), max(0, dx)),
                         (max(0, dy) + Y, max(0, dx) + X))


def edge_masked(v, ds, dy, dx, z, N, yg, xg, NY, NX, fill):
    """``v`` with ``fill`` wherever the offset (ds, dy, dx) leaves the
    global domain, judged in GLOBAL coordinates on all three axes: a
    local edge that is *not* a domain edge — a tile or block seam —
    keeps the neighbor value its caller's ghost layers carried in."""
    if ds == -1:
        v = jnp.where(z == 0, fill, v)
    elif ds == 1:
        v = jnp.where(z == N - 1, fill, v)
    if dy == -1:
        v = jnp.where(yg == 0, fill, v)
    elif dy == 1:
        v = jnp.where(yg == NY - 1, fill, v)
    if dx == -1:
        v = jnp.where(xg == 0, fill, v)
    elif dx == 1:
        v = jnp.where(xg == NX - 1, fill, v)
    return v


def _neighbor_scan(win, lay, z, N, yg, xg, NY, NX, lin, offs, *,
                   ascending: bool):
    """Returns (best_code, is_extremum) for the SoS-steepest neighbor.

    Off-domain fills are ±inf in the field dtype (not f32 literals), so
    f64 fields classify boundary extrema correctly. Candidates are
    stacked and reduced via ``grid._sos_argbest`` — a chained
    compare-and-select scan would compile exponentially on XLA:CPU (see
    that helper's docstring); the stacked form is bitwise identical.
    """
    # mszlint: disable=transfer-discipline -- kernel-body helper, only ever
    # called under trace where the constant folds at trace time
    fill = jnp.asarray(-jnp.inf if ascending else jnp.inf, win[1].dtype)
    vals = [win[1]]
    idxs = [lin]
    for ds, dy, dx in offs:
        v = neighbor(win, lay, ds, dy, dx, fill)
        vals.append(edge_masked(v, ds, dy, dx, z, N, yg, xg, NY, NX, fill))
        idxs.append(lin + (ds * NY + dy) * NX + dx)
    slot = _sos_argbest(jnp.stack(vals), jnp.stack(idxs), ascending=ascending)
    best_c = jnp.where(slot == 0, jnp.int32(len(offs)), slot - 1)
    return best_c, slot == 0


def _kernel(origin_c, g_m, g_c, g_p, Mf_m, Mf_c, Mf_p, mf_m, mf_c, mf_p,
            maxf_c, minf_c,
            up_out, dn_out, self_out, demote_out, promote_out,
            *, N, NY, NX, lay, offs):
    z, yg, xg = program_coords(origin_c, lay)
    lin = z * (NY * NX) + yg * NX + xg

    def plane(ref):
        return ref[...].reshape(lay.P, lay.X)

    g_win = load_window((g_m, g_c, g_p), lay)
    up_c, is_max_g = _neighbor_scan(g_win, lay, z, N, yg, xg, NY, NX, lin,
                                    offs, ascending=True)
    dn_c, is_min_g = _neighbor_scan(g_win, lay, z, N, yg, xg, NY, NX, lin,
                                    offs, ascending=False)

    is_max_f = plane(maxf_c) != 0
    is_min_f = plane(minf_c) != 0

    # gather original labels at the g-steepest neighbor (Eq. 6 predicates)
    def gather_dir(win, code):
        out = win[1]
        for k, (ds, dy, dx) in enumerate(offs):
            out = jnp.where(code == k, neighbor(win, lay, ds, dy, dx, 0),
                            out)
        return out

    Mf_win = load_window((Mf_m, Mf_c, Mf_p), lay)
    mf_win = load_window((mf_m, mf_c, mf_p), lay)
    M_next = gather_dir(Mf_win, up_c)
    m_next = gather_dir(mf_win, dn_c)

    fpmax = is_max_g & ~is_max_f
    fpmin = is_min_g & ~is_min_f
    fnmax = ~is_max_g & is_max_f
    fnmin = ~is_min_g & is_min_f
    trouble_max = ~is_max_g & (M_next != Mf_win[1])
    trouble_min = ~is_min_g & (m_next != mf_win[1])

    up_out[...] = up_c.reshape(up_out.shape)
    dn_out[...] = dn_c.reshape(dn_out.shape)
    self_out[...] = (fpmax | fnmin).astype(jnp.int32).reshape(self_out.shape)
    demote_out[...] = ((fnmax | trouble_max).astype(jnp.int32)
                       .reshape(demote_out.shape))
    promote_out[...] = ((fpmin | trouble_min).astype(jnp.int32)
                        .reshape(promote_out.shape))


def typed_operand(v, dtype) -> jnp.ndarray:
    """Normalize a scalar operand — python number or traced/device
    value — to a device scalar of ``dtype``. Host values move via the
    EXPLICIT ``jax.device_put`` API: the kernel entry points are called
    eagerly, where an implicit ``jnp.asarray(number)`` conversion would
    trip ``debug.no_transfers()`` on every dispatch; device values just
    cast in place."""
    if isinstance(v, jnp.ndarray):
        return v.astype(dtype)
    import numpy as np
    return jax.device_put(np.asarray(v, dtype))


def _int32_operand(v) -> jnp.ndarray:
    return typed_operand(v, jnp.int32)


def slab_lo_operand(slab_lo) -> jnp.ndarray:
    """Normalize a slab offset — python int or traced int32 scalar (the
    sharded fix loop passes ``axis_index * block - 1``) — to the (1, 1)
    operand the kernels read. Static and traced offsets produce bitwise
    identical outputs; only the specialization key differs."""
    return _int32_operand(slab_lo).reshape(1, 1)


def slab_lo_spec() -> pl.BlockSpec:
    """Every grid program sees the same (1, 1) slab-offset block."""
    return pl.BlockSpec((1, 1), lambda z: (0, 0))


def origin_operand(slab_lo, row_lo=0, col_lo=0) -> jnp.ndarray:
    """Normalize a 3-component tile origin (slab, plane row, plane col)
    — python ints or traced int32 scalars — to the (1, 3) operand the
    stencil kernels read. The sharded backend passes each component as
    ``axis_index * block - halo`` so one SPMD program serves every block
    of a 2D/3D block mesh; static and traced origins produce bitwise
    identical outputs, only the specialization key differs."""
    parts = [_int32_operand(v).reshape(1) for v in
             (slab_lo, row_lo, col_lo)]
    return jnp.concatenate(parts).reshape(1, 3)


def origin_spec() -> pl.BlockSpec:
    """Every grid program sees the same (1, 3) tile-origin block."""
    return pl.BlockSpec((1, 3), lambda z: (0, 0))


def _axis_total(total, lo, extent: int, what: str) -> int:
    """Resolve a global axis extent: explicit ``total`` wins; otherwise
    the tile is assumed flush with the domain end (``lo + extent``),
    which requires a static ``lo``."""
    if total is None:
        if not isinstance(lo, int):
            raise ValueError(
                f"a traced {what} offset needs an explicit total extent")
        return lo + extent
    # mszlint: disable=transfer-discipline -- total is a host int parameter
    return int(total)


def global_extents(shape, slab_lo, n_slabs_total, row_lo, n_rows_total,
                   col_lo, n_cols_total) -> Tuple[int, int, int]:
    """(N, NY, NX): the global slab count and plane extents a tile of
    ``shape`` sits in. 2D fields place their rows with the slab pair and
    their columns with the col pair; they are one slab (N = 1)."""
    if len(shape) == 3:
        n, P, X = shape
        return (_axis_total(n_slabs_total, slab_lo, n, "slab"),
                _axis_total(n_rows_total, row_lo, P, "row"),
                _axis_total(n_cols_total, col_lo, X, "col"))
    Y, X = shape
    return (1, _axis_total(n_slabs_total, slab_lo, Y, "slab"),
            _axis_total(n_cols_total, col_lo, X, "col"))


def extrema_masks_pallas(g: jnp.ndarray, M_f: jnp.ndarray, m_f: jnp.ndarray,
                         is_max_f: jnp.ndarray, is_min_f: jnp.ndarray,
                         *, interpret: bool | None = None,
                         slab_lo=0, n_slabs_total: int | None = None,
                         row_lo=0, col_lo=0,
                         n_rows_total: int | None = None,
                         n_cols_total: int | None = None):
    """g: (Z,Y,X) or (Y,X) float; M_f/m_f: int32 labels of the original
    field; is_max_f/min_f: int32 0/1. Returns (up_c, dn_c, self_edit,
    demote_src, promote_src), all int32 of g's shape.

    ``slab_lo``/``n_slabs_total`` place a tile of the leading axis inside
    a larger field (global index of g[0], and the field's total extent);
    ``row_lo``/``col_lo`` with ``n_rows_total``/``n_cols_total`` do the
    same for the plane axes, placing a 2D/3D *block* of the sharded
    backend inside the global field (2D fields use the col pair for
    their second axis; the row pair is unused). Offsets may be traced
    int32 scalars (one SPMD program serves every shard of a sharded
    run); the matching total is then required.
    """
    if interpret is None:
        interpret = default_interpret()
    if g.ndim not in (2, 3):
        raise ValueError(f"extrema kernel supports 2D/3D, got shape {g.shape}")
    N, NY, NX = global_extents(g.shape, slab_lo, n_slabs_total, row_lo,
                               n_rows_total, col_lo, n_cols_total)
    lay = slab_layout(g.shape)
    halo, center = list(lay.halo), lay.center
    out_shape = [jax.ShapeDtypeStruct(g.shape, jnp.int32)] * 5
    kern = functools.partial(_kernel, N=N, NY=NY, NX=NX, lay=lay,
                             offs=slab_offsets(g.ndim))
    nbytes = (lay.block_bytes(g.dtype.itemsize, halo=1, center=0)
              + lay.block_bytes(4, halo=2, center=7))
    temps = TEMP_PLANES["extrema", g.ndim] * lay.plane_bytes(4)
    return pl.pallas_call(
        kern,
        grid=(lay.grid,),
        in_specs=[origin_spec()] + halo + halo + halo + [center, center],
        out_specs=[center] * 5,
        out_shape=out_shape,
        compiler_params=(None if interpret
                         else vmem_params(nbytes, temps, "extrema kernel")),
        interpret=interpret,
    )(origin_operand(slab_lo, row_lo, col_lo), g, g, g,
      M_f, M_f, M_f, m_f, m_f, m_f,
      is_max_f.astype(jnp.int32), is_min_f.astype(jnp.int32))
