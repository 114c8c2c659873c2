"""Pure-jnp oracles for the Pallas kernels (the kernels must match these
bit-for-bit; swept in tests/test_kernels.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import fixes, grid


def extrema_masks_ref(g, M_f, m_f, is_max_f, is_min_f):
    """Oracle for kernels.extrema.extrema_masks_pallas."""
    up_c, dn_c = grid.steepest_dirs(g)
    sc = grid.self_code(g.ndim)
    is_max_g = up_c == sc
    is_min_g = dn_c == sc
    M_next = grid.gather_dir(M_f, up_c)
    m_next = grid.gather_dir(m_f, dn_c)
    fpmax = is_max_g & ~is_max_f
    fpmin = is_min_g & ~is_min_f
    fnmax = ~is_max_g & is_max_f
    fnmin = ~is_min_g & is_min_f
    trouble_max = ~is_max_g & (M_next != M_f)
    trouble_min = ~is_min_g & (m_next != m_f)
    return (up_c, dn_c,
            (fpmax | fnmin).astype(jnp.int32),
            (fnmax | trouble_max).astype(jnp.int32),
            (fpmin | trouble_min).astype(jnp.int32))


def fix_pass_ref(g, lower, self_edit, demote_src, promote_src,
                 up_code_g, dn_code_f):
    """Oracle for kernels.fixpass.fix_pass_pallas (g_next only)."""
    target = ((self_edit != 0)
              | fixes._pull(demote_src != 0, up_code_g)
              | fixes._pull(promote_src != 0, dn_code_f))
    g2 = fixes._halve_toward_lower(g, lower, target)
    viol = (jnp.sum(self_edit) + jnp.sum(demote_src)
            + jnp.sum(promote_src)).astype(jnp.int32)
    return g2, viol


def lorenzo_quant_ref(f, step):
    """Oracle for kernels.lorenzo.lorenzo_quant_pallas. Divides by step
    (not multiply-by-reciprocal) — the canonical quantization arithmetic
    shared with the host codec (szlike module docstring)."""
    q = jnp.round(f / step).astype(jnp.int32)
    r = q
    for ax in range(f.ndim):
        shifted = jnp.concatenate(
            [jnp.zeros_like(jax.lax.slice_in_dim(r, 0, 1, axis=ax)),
             jax.lax.slice_in_dim(r, 0, r.shape[ax] - 1, axis=ax)], axis=ax)
        r = r - shifted
    return r
