"""Pallas TPU kernel: 'fix false critical points' — the pull-based edit
application (paper Section 6.1, atomicCAS replaced by a gather + min
reduction; see DESIGN.md §2).

Each vertex j decreases to (g_j + lower_j)/2 (to lower_j where that
rounds back to g_j) iff
  * j is its own fix target (self_edit[j]), or
  * a stencil neighbor i has demote_src[i] and up_code_g[i] pointing at j, or
  * a stencil neighbor i has promote_src[i] and dn_code_f[i] pointing at j.

Same slab layout as the extrema kernel (3D: z-slabs; 2D: row blocks
with a row halo), including the global-coordinate placement for tiled
execution. Also emits per-slab violation (fix-source) and edit-target
counts — one per z-slab in 3D, one per row in 2D: the source counts are
the paper's lock-free work-queue height turned into a reduction, and
the target counts (targets whose value moved) are the dirty-slab bitmap
the worklist drivers
(DESIGN.md §7) use to skip slabs whose neighborhood did not change last
pass. The counts leave each program as an (S, 1) column block (S slabs
or rows per program), which meets Mosaic's tiling rule at any S; masks
are combined as int32 0/1 values, since Mosaic cannot truncate to
booleans in every position."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .extrema import (TEMP_PLANES, default_interpret, edge_masked,
                      global_extents, load_window, neighbor, origin_operand,
                      origin_spec, program_coords, slab_layout, slab_offsets,
                      vmem_params)

# code k is stored at i; i targets j = i + off_k. From j's view the source
# sits at -off_k and must carry code k.


def _kernel(origin_c, g_c, low_c, self_c,
            dem_m, dem_c, dem_p, pro_m, pro_c, pro_p,
            upg_m, upg_c, upg_p, dnf_m, dnf_c, dnf_p,
            g_out, viol_out, tgt_out, *, N, NY, NX, lay, offs):
    z, yg, xg = program_coords(origin_c, lay)

    def plane(ref):
        return ref[...].reshape(lay.P, lay.X)

    def pulled(src_win, code_win):
        out = jnp.zeros((lay.P, lay.X), jnp.int32)
        for k, (ds, dy, dx) in enumerate(offs):
            m = neighbor(src_win, lay, -ds, -dy, -dx, 0)
            c = neighbor(code_win, lay, -ds, -dy, -dx, -1)
            # a pull source must lie inside the real domain, checked in
            # GLOBAL coordinates: at a block seam the shifted value is
            # ghost data (valid), at a true domain edge the zero fill
            # already cleared m and the mask is a no-op
            m = edge_masked(m, -ds, -dy, -dx, z, N, yg, xg, NY, NX, 0)
            out = out | jnp.where((m != 0) & (c == k), 1, 0)
        return out

    dem = load_window((dem_m, dem_c, dem_p), lay)
    pro = load_window((pro_m, pro_c, pro_p), lay)
    upg = load_window((upg_m, upg_c, upg_p), lay)
    dnf = load_window((dnf_m, dnf_c, dnf_p), lay)

    self_p = plane(self_c)
    target = (jnp.where(self_p != 0, 1, 0)
              | pulled(dem, upg) | pulled(pro, dnf))
    g = plane(g_c)
    low = plane(low_c)
    new = jnp.maximum((g + low) * jnp.asarray(0.5, g.dtype), low)
    # a halving that rounds back to g goes to the bound (core.backend
    # .halve_toward_lower), so every edit of a value above it moves it
    new = jnp.where(new == g, low, new)
    moved = jnp.where((target != 0) & (new != g), 1, 0)
    g_out[...] = jnp.where(target != 0, new, g).reshape(g_out.shape)

    def counts(x):
        """Per-slab (3D) or per-row (2D) sums of ``x``, as an (S, 1)
        column."""
        rows = jnp.sum(x, axis=1, keepdims=True)
        if lay.ndim == 3:
            rows = jnp.sum(rows, axis=0, keepdims=True)
        return rows.reshape(viol_out.shape)

    viol_out[...] = counts(self_p + dem[1] + pro[1])
    tgt_out[...] = counts(moved)


def fix_pass_pallas(g, lower, self_edit, demote_src, promote_src,
                    up_code_g, dn_code_f, *, interpret: bool | None = None,
                    slab_lo=0, n_slabs_total: int | None = None,
                    row_lo=0, col_lo=0,
                    n_rows_total: int | None = None,
                    n_cols_total: int | None = None):
    """Apply one fused fix pass. All inputs (Z,Y,X) or (Y,X); masks int32
    0/1. Returns (g_next of g's shape/dtype, viol (n,) int32 per-slab
    fix-SOURCE counts, tgt (n,) int32 per-slab counts of edit TARGETS
    whose value moved), n being the leading extent (z-slabs in 3D, rows
    in 2D). ``viol`` drives convergence (0 sources everywhere == done);
    ``tgt`` is the loop's progress (sources with no moved value is a
    stall, DESIGN.md §2) and feeds the dirty-slab worklists (DESIGN.md
    §7): a slab where nothing moved last pass — nor in its 2-slab
    neighborhood — produces bitwise-identical masks this pass and can be
    skipped.
    ``slab_lo``/``n_slabs_total`` and ``row_lo``/``col_lo`` with
    ``n_rows_total``/``n_cols_total`` as in the extrema kernel (offsets
    may be traced; the matching total is then required)."""
    if interpret is None:
        interpret = default_interpret()
    if g.ndim not in (2, 3):
        raise ValueError(f"fix kernel supports 2D/3D, got shape {g.shape}")
    N, NY, NX = global_extents(g.shape, slab_lo, n_slabs_total, row_lo,
                               n_rows_total, col_lo, n_cols_total)
    lay = slab_layout(g.shape)
    halo, center = list(lay.halo), lay.center
    n = g.shape[0]
    count_spec = pl.BlockSpec((1, lay.S, 1), lambda i: (i, 0, 0))
    count_shape = jax.ShapeDtypeStruct((lay.grid, lay.S, 1), jnp.int32)
    out_specs = [center, count_spec, count_spec]
    out_shape = [jax.ShapeDtypeStruct(g.shape, g.dtype),
                 count_shape, count_shape]
    kern = functools.partial(_kernel, N=N, NY=NY, NX=NX, lay=lay,
                             offs=slab_offsets(g.ndim))
    nbytes = (lay.block_bytes(g.dtype.itemsize, halo=0, center=3)
              + lay.block_bytes(4, halo=4, center=1)
              + 2 * lay.S * 128 * 4)
    temps = TEMP_PLANES["fixpass", g.ndim] * lay.plane_bytes(4)
    g2, viol, tgt = pl.pallas_call(
        kern,
        grid=(lay.grid,),
        in_specs=([origin_spec(), center, center, center]
                  + halo + halo + halo + halo),
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=(None if interpret
                         else vmem_params(nbytes, temps, "fix-pass kernel")),
        interpret=interpret,
    )(origin_operand(slab_lo, row_lo, col_lo), g, lower, self_edit,
      demote_src, demote_src, demote_src,
      promote_src, promote_src, promote_src,
      up_code_g, up_code_g, up_code_g,
      dn_code_f, dn_code_f, dn_code_f)
    return g2, viol.reshape(-1)[:n], tgt.reshape(-1)[:n]
