"""Pallas TPU kernel: dual-quantization Lorenzo transform (the SZ-like
compressor's hot loop, repro.compress.szlike) for 2D and 3D fields.

3D:  r[z,y,x] = q - q(z-1) - q(y-1) - q(x-1) + q(z-1,y-1) + q(z-1,x-1)
              + q(y-1,x-1) - q(z-1,y-1,x-1),   q = round(f / step)
2D:  r[y,x]   = q - q(y-1) - q(x-1) + q(y-1,x-1)

Slab decomposition mirrors the extrema/fix kernels (3D: z-slabs of plane
shape (Y, X); 2D: row blocks with a row halo, ``extrema.slab_layout``),
but Lorenzo is backward-only: each program reads its own block and the
one below it (slab s-1, or the 8 rows above) plus static in-plane
shifts. The
quantization ``round(f / step)`` runs in the field's dtype — the shared
arithmetic contract with the host codec (szlike module docstring), so the
int32 residuals match the host's bit for bit within the int32 range
precondition.

``step`` and ``slab_lo`` are scalar OPERANDS, not static parameters:
``step`` so batched execution can vmap one compiled kernel over
per-member quantization steps, ``slab_lo`` (traced-capable, like the
extrema kernel's) so the sharded backend can transform its own Z-slab in
global coordinates — the q(z-1) term is zeroed at the TRUE domain
boundary z == 0 only, not at slab edges.

The inverse (d nested cumsums) stays XLA-level (szlike.sz_inverse): a
slab-carry ``lax.scan`` along the leading axis — O(n) and cache-friendly
where XLA's log-depth cumsum rewrite strides badly — and native cumsums
elsewhere; a hand-rolled Pallas kernel would only re-derive them."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .extrema import (TEMP_PLANES, _shift2d, default_interpret,
                      load_window, neighbor, origin_operand, origin_spec,
                      program_coords, slab_layout, typed_operand,
                      vmem_params)


def _kernel(origin_c, step_c, f_m, f_c, r_out, *, lay):
    z, yg, _ = program_coords(origin_c, lay)
    step = step_c[0, 0]
    win = tuple(jnp.round(a / step).astype(jnp.int32)
                for a in load_window((f_m, f_c, None), lay)[:2])

    def sh(a, dy, dx):
        return _shift2d(a, dy, dx, 0)

    qc = win[1]
    if lay.ndim == 3:
        qm = jnp.where(z == 0, 0, win[0])  # zero-pad before the domain
        r = (qc
             - sh(qc, -1, 0) - sh(qc, 0, -1) - qm
             + sh(qm, -1, 0) + sh(qm, 0, -1) + sh(qc, -1, -1)
             - sh(qm, -1, -1))
    else:                                  # 2D: q(y-1) reaches the halo row
        qm = jnp.where(yg == 0, 0, neighbor(win, lay, 0, -1, 0, 0))
        r = qc - sh(qc, 0, -1) - qm + sh(qm, 0, -1)
    r_out[...] = r.reshape(r_out.shape)


def lorenzo_quant_pallas(f: jnp.ndarray, step, *,
                         interpret: bool | None = None,
                         slab_lo=0) -> jnp.ndarray:
    """f: (Z,Y,X) or (Y,X) float; returns int32 Lorenzo residuals of
    round(f / step).

    ``slab_lo`` places a block of the leading axis inside a larger field
    exactly as in ``extrema_masks_pallas`` (no ``n_slabs_total`` — the
    stencil is backward-only, so only the leading axis's 0 boundary
    matters). It may be a traced int32 scalar (the sharded transform
    passes ``axis_index * L - 1``); outputs whose backward 1-vertex halo
    lies inside the block are bitwise identical to an unblocked run.
    """
    if interpret is None:
        interpret = default_interpret()
    if f.ndim not in (2, 3):
        raise ValueError(f"lorenzo kernel supports 2D/3D, got shape {f.shape}")
    lay = slab_layout(f.shape)
    step_op = typed_operand(step, f.dtype).reshape(1, 1)
    nbytes = (lay.plane_bytes(f.dtype.itemsize)
              + lay.edge_bytes(f.dtype.itemsize) + lay.plane_bytes(4))
    temps = TEMP_PLANES["lorenzo", f.ndim] * lay.plane_bytes(4)
    return pl.pallas_call(
        functools.partial(_kernel, lay=lay),
        grid=(lay.grid,),
        in_specs=[origin_spec(), pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  lay.halo[0], lay.halo[1]],
        out_specs=lay.center,
        out_shape=jax.ShapeDtypeStruct(f.shape, jnp.int32),
        compiler_params=(None if interpret
                         else vmem_params(nbytes, temps, "Lorenzo kernel")),
        interpret=interpret,
    )(origin_operand(slab_lo), step_op, f, f)
