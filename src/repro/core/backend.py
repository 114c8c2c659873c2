"""Unified stencil-backend dispatch for the MSz fix loop and the
device-resident base transform.

One protocol, many execution strategies (see DESIGN.md §3): every
backend exposes the two stencil stages of the fused fix iteration,

  * ``extrema_masks(g, topo)``  — 'update directions' + 'find false
    critical points' fused (the paper's two dominant components, Table 1)
  * ``fix_pass(g, topo, masks)`` — the pull-based conflict-free edit
    application (DESIGN.md §2)

plus ``fused_step`` composing them into one (g_next, n_violations)
iteration, and — since the device-resident compression path
(DESIGN.md §4) — the SZ-like base transform pair,

  * ``transform(f, step)``          — quantize + integer Lorenzo
    -> int32 residual codes (the cuSZ dual-quantization forward pass)
  * ``reconstruct(r, step, dtype)`` — d nested int32 cumsums + dequant
    -> f_hat, bitwise equal to the host codec's ``sz_decompress`` of the
    same codes (int32 range precondition: szlike.check_int32_range)

so ``f_hat`` flows from residual codes straight into the fix loop
without leaving the device, and — since the device-resident
DECOMPRESSION path (DESIGN.md §5) — the read-side mirror,

  * ``scatter_edits(f_hat, idx, val)`` — jitted scatter-add of the edit
    deltas, g = f_hat + delta, bitwise equal to the host path's
    ``driver.apply_edits`` (unique indices; OOB indices drop, so batched
    callers can pad edit streams)

Registered implementations:

  * ``reference`` — pure-jnp dense stencils (XLA-fused; the former
    ``fixes.fused_pass`` body lives here)
  * ``pallas``    — the slab-decomposed Pallas TPU kernels
    (``kernels.extrema`` / ``kernels.fixpass``), interpret mode off-TPU,
    with pMSz-style Z-tiling for fields above a VMEM slab budget
  * ``sharded``   — the same kernels distributed over the ``data`` axis
    of a device mesh under shard_map with per-iteration ppermute halo
    exchange (``repro.distributed.shardfix``, registered lazily)

Backends must be bitwise-interchangeable: same g trajectory, same
violation counts, same iteration count (tests/test_backend.py and
tests/test_shardfix.py enforce this). ``resolve_backend("auto", ...)``
picks ``sharded`` when a mesh with >= 2 data-axis devices is given or
active, else ``pallas`` whenever the input is supported, and falls back
to ``reference`` otherwise.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from . import grid


def _device_scalar(step, dtype) -> jnp.ndarray:
    """The quantization step as a device scalar of ``dtype``.

    ``transform``/``reconstruct`` are called eagerly (outside jit) with a
    host float, and an eager ``jnp.asarray(step, dtype)`` is an IMPLICIT
    host->device transfer — it trips ``debug.no_transfers()``. Route the
    host case through the explicit ``jax.device_put`` API instead
    (identical dtype canonicalization, so the codes are bitwise
    unchanged); values already on device just cast in place."""
    if isinstance(step, jnp.ndarray):
        return step.astype(dtype)
    import numpy as _np
    return jax.device_put(_np.asarray(step, dtype))


# ---------------------------------------------------------------------------
# shared stencil predicates (pure jnp — also reused by the paper-mode loops
# in fixes.py)
# ---------------------------------------------------------------------------

class FalseMasks(NamedTuple):
    fpmax: jnp.ndarray
    fpmin: jnp.ndarray
    fnmax: jnp.ndarray
    fnmin: jnp.ndarray
    up_c_g: jnp.ndarray
    dn_c_g: jnp.ndarray


def false_critical_masks(g: jnp.ndarray, topo) -> FalseMasks:
    """Definitions 1-3: the four false critical point classes."""
    up_c_g, dn_c_g = grid.steepest_dirs(g)
    sc = grid.self_code(g.ndim)
    is_max_g = up_c_g == sc
    is_min_g = dn_c_g == sc
    return FalseMasks(
        fpmax=is_max_g & ~topo.is_max,
        fpmin=is_min_g & ~topo.is_min,
        fnmax=~is_max_g & topo.is_max,
        fnmin=~is_min_g & topo.is_min,
        up_c_g=up_c_g,
        dn_c_g=dn_c_g,
    )


def trouble_masks(g_codes: FalseMasks, topo):
    """Local R-loop predicates (our vectorized troublemaker test).

    trouble_max(t): t non-max in g and its g-ascending edge leaves t's
    original ascending region -> demote the wrong winner dir_up_g(t).
    trouble_min(t): symmetric on the descending side -> promote (decrease)
    the ORIGINAL descending neighbor dir_dn_f(t). Only decreasing edits can
    'promote' a descent target, hence the asymmetry (see DESIGN.md §2).
    """
    sc = grid.self_code(topo.M.ndim)
    nonmax_g = g_codes.up_c_g != sc
    nonmin_g = g_codes.dn_c_g != sc
    M_next = grid.gather_dir(topo.M, g_codes.up_c_g)
    m_next = grid.gather_dir(topo.m, g_codes.dn_c_g)
    trouble_max = nonmax_g & (M_next != topo.M)
    trouble_min = nonmin_g & (m_next != topo.m)
    return trouble_max, trouble_min


def _halve_toward_lower(g, lower, mask):
    """Eq. 2/3/4/5/6 decreasing edit, clamped so |f-g|<=xi holds exactly."""
    new = jnp.maximum((g + lower) * jnp.asarray(0.5, g.dtype), lower)
    return jnp.where(mask, new, g)


def _pull(src_mask: jnp.ndarray, code: jnp.ndarray) -> jnp.ndarray:
    """pulled[j] = OR_k ( src_mask[j - off_k] & code[j - off_k] == k ).

    Dense 'pull' equivalent of the paper's atomic scatter: a vertex j is an
    edit target iff some stencil neighbor i has ``src_mask[i]`` set and i's
    direction code points at j.
    """
    offs = grid.offsets_for(src_mask.ndim)
    out = jnp.zeros(src_mask.shape, bool)
    for k, off in enumerate(offs):
        noff = tuple(-o for o in off)
        m = grid.shift(src_mask, noff, False)
        c = grid.shift(code, noff, jnp.int32(-1))
        out = out | (m & (c == k))
    return out


# ---------------------------------------------------------------------------
# the backend protocol
# ---------------------------------------------------------------------------

class StencilMasks(NamedTuple):
    """Outputs of one extrema/false-point classification pass.

    ``dn_c_f`` is the ORIGINAL field's descending codes (copied out of
    the topo so ``fix_pass`` needs only (g, topo, masks)); the fix-source
    masks follow the fused formulation of fixes.py: self_edit = FPmax |
    FNmin, demote_src = FNmax | trouble_max, promote_src = FPmin |
    trouble_min.
    """
    up_c_g: jnp.ndarray
    dn_c_g: jnp.ndarray
    self_edit: jnp.ndarray
    demote_src: jnp.ndarray
    promote_src: jnp.ndarray
    dn_c_f: jnp.ndarray

    @property
    def n_violations(self) -> jnp.ndarray:
        """Total fix sources — 0 iff the fused loop has converged."""
        return (jnp.sum(self.self_edit) + jnp.sum(self.demote_src)
                + jnp.sum(self.promote_src)).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class ReferenceBackend:
    """Dense pure-jnp stencils (the seed implementation, XLA-fused)."""
    name: str = "reference"

    def supports(self, shape: Tuple[int, ...], dtype) -> bool:
        """Any 2D/3D field (the dense stencils are shape-agnostic)."""
        return len(shape) in (2, 3)

    def extrema_masks(self, g: jnp.ndarray, topo) -> StencilMasks:
        """Classification pass: direction codes + the fused fix-source
        masks of one iteration (see StencilMasks)."""
        fm = false_critical_masks(g, topo)
        t_max, t_min = trouble_masks(fm, topo)
        return StencilMasks(
            up_c_g=fm.up_c_g,
            dn_c_g=fm.dn_c_g,
            self_edit=fm.fpmax | fm.fnmin,
            demote_src=fm.fnmax | t_max,
            promote_src=fm.fpmin | t_min,
            dn_c_f=topo.dn_c,
        )

    def fix_pass(self, g: jnp.ndarray, topo, masks: StencilMasks):
        """Conflict-free pull-based edit application (DESIGN.md §2):
        (g_next, n_violations)."""
        target = ((masks.self_edit != 0)
                  | _pull(masks.demote_src != 0, masks.up_c_g)
                  | _pull(masks.promote_src != 0, masks.dn_c_f))
        return _halve_toward_lower(g, topo.lower, target), masks.n_violations

    def fused_step(self, g: jnp.ndarray, topo):
        """One fused fix iteration: (g_next, n_violations)."""
        masks = self.extrema_masks(g, topo)
        return self.fix_pass(g, topo, masks)

    # -- device-resident base transform (DESIGN.md §4) ----------------
    def transform(self, f: jnp.ndarray, step) -> jnp.ndarray:
        """Quantize + integer Lorenzo -> int32 residual codes."""
        from ..compress.szlike import _sz_transform_jit
        return _sz_transform_jit(f, _device_scalar(step, f.dtype))

    def reconstruct(self, r: jnp.ndarray, step, dtype) -> jnp.ndarray:
        """int32 residual codes -> f_hat in ``dtype`` (bitwise equal to
        the host codec's reconstruction of the same codes)."""
        from ..compress.szlike import sz_inverse
        return sz_inverse(r, _device_scalar(step, dtype))

    # -- device-resident decompression path (DESIGN.md §5) ------------
    def scatter_edits(self, f_hat: jnp.ndarray, idx, val) -> jnp.ndarray:
        """g = f_hat + delta via one jitted scatter-add (XLA-native; a
        Pallas kernel buys nothing for an irregular sparse scatter)."""
        from .driver import apply_edits_device
        return apply_edits_device(f_hat, idx, val)

    # -- on-device entropy codec (DESIGN.md §8) ------------------------
    def pack_codes(self, r: jnp.ndarray):
        """int32 residual codes -> chunked-bitplane stream
        ``(words, bits, n_words)`` (pure-jnp codec; see
        ``repro.kernels.pack``)."""
        from ..kernels.pack import pack_codes_jnp
        return pack_codes_jnp(r)

    def unpack_codes(self, words, bits, shape: Tuple[int, ...]
                     ) -> jnp.ndarray:
        """Inverse of ``pack_codes``: the int32 code array of ``shape``
        from a packed stream."""
        from ..kernels.pack import unpack_codes_jnp
        return unpack_codes_jnp(words, bits, tuple(shape))


@dataclasses.dataclass(frozen=True)
class PallasBackend:
    """Slab-decomposed Pallas kernels (kernels.extrema / kernels.fixpass).

    ``z_tile``: slabs per tile for pMSz-style Z-tiling (None = tile only
    when the field exceeds ``vmem_slab_budget`` slabs per pallas_call).
    Tiled and untiled runs are bitwise identical: each iteration re-slices
    every tile with a fresh 2-slab input halo (halo re-exchange), the
    kernels evaluate boundaries in global coordinates, and only interior
    slabs are kept.

    ``interpret``: None = auto (lowered on TPU/GPU, interpreted
    elsewhere; ``MSZ_PALLAS_INTERPRET`` overrides — see
    ``kernels.extrema.default_interpret``).

    ``worklist`` / ``worklist_group`` / ``worklist_min_slabs``: the
    dirty-slab worklist loop (DESIGN.md §7). ``None`` engages it
    automatically for solo fix loops on fields of at least
    ``worklist_min_slabs`` slabs; True/False force it. The slab axis is
    split into groups of ``worklist_group`` slabs, and each iteration
    re-runs the stencils only on groups within 2 slabs of an edit target
    of the previous iteration (``lax.cond`` keeps the skip inside jit) —
    bitwise identical to the dense loop, because a slab's fresh masks are
    a function of g on its 2-slab neighborhood and untouched
    neighborhoods reproduce last iteration's masks exactly.
    """
    name: str = "pallas"
    z_tile: Optional[int] = None
    vmem_slab_budget: int = 256
    interpret: Optional[bool] = None
    worklist: Optional[bool] = None
    worklist_group: int = 8
    worklist_min_slabs: int = 64

    def supports(self, shape: Tuple[int, ...], dtype) -> bool:
        """Non-empty 2D/3D floating-point fields (slab kernels)."""
        return (len(shape) in (2, 3) and min(shape) >= 1
                and jnp.issubdtype(jnp.dtype(dtype), jnp.floating))

    def _interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        from ..kernels.extrema import default_interpret
        return default_interpret()

    # -- untiled protocol methods -------------------------------------
    def extrema_masks(self, g: jnp.ndarray, topo, *,
                      slab_lo: int = 0,
                      n_slabs_total: Optional[int] = None) -> StencilMasks:
        """Classification pass via the slab kernel; ``slab_lo`` /
        ``n_slabs_total`` place a tile in global coordinates."""
        from ..kernels.extrema import extrema_masks_pallas
        up_c, dn_c, selfe, dem, pro = extrema_masks_pallas(
            g, topo.M, topo.m,
            topo.is_max.astype(jnp.int32), topo.is_min.astype(jnp.int32),
            interpret=self._interpret(), slab_lo=slab_lo,
            n_slabs_total=n_slabs_total)
        return StencilMasks(up_c, dn_c, selfe, dem, pro, topo.dn_c)

    def fix_pass(self, g: jnp.ndarray, topo, masks: StencilMasks):
        """Pull-based edit application via the slab kernel:
        (g_next, n_violations)."""
        from ..kernels.fixpass import fix_pass_pallas
        g2, viol, _ = fix_pass_pallas(
            g, topo.lower, masks.self_edit, masks.demote_src,
            masks.promote_src, masks.up_c_g, masks.dn_c_f,
            interpret=self._interpret())
        return g2, jnp.sum(viol).astype(jnp.int32)

    # -- fused iteration, tiled when needed ---------------------------
    def _pick_tile(self, n_slabs: int) -> int:
        if self.z_tile is not None:
            return max(int(self.z_tile), 1)
        return n_slabs if n_slabs <= self.vmem_slab_budget \
            else self.vmem_slab_budget

    def fused_step(self, g: jnp.ndarray, topo):
        """One fused fix iteration: (g_next, n_violations), Z-tiled
        when the field exceeds the VMEM slab budget."""
        tile = self._pick_tile(g.shape[0])
        if tile >= g.shape[0]:
            masks = self.extrema_masks(g, topo)
            return self.fix_pass(g, topo, masks)
        return self._tiled_step(g, topo, tile)

    # -- device-resident base transform (DESIGN.md §4) ----------------
    def transform(self, f: jnp.ndarray, step) -> jnp.ndarray:
        """Quantize + integer Lorenzo via the slab kernel. No Z-tiling:
        the pallas_call grid already streams slab pairs through VMEM, so
        the footprint is ~2 slabs regardless of field height."""
        from ..kernels.lorenzo import lorenzo_quant_pallas
        return lorenzo_quant_pallas(f, _device_scalar(step, f.dtype),
                                    interpret=self._interpret())

    def reconstruct(self, r: jnp.ndarray, step, dtype) -> jnp.ndarray:
        """Inverse stays XLA-level (kernels.lorenzo docstring) —
        identical arithmetic to the reference backend."""
        from ..compress.szlike import sz_inverse
        return sz_inverse(r, _device_scalar(step, dtype))

    # -- device-resident decompression path (DESIGN.md §5) ------------
    def scatter_edits(self, f_hat: jnp.ndarray, idx, val) -> jnp.ndarray:
        """Same XLA-native scatter-add as the reference backend (sparse
        irregular scatter has no slab structure to exploit)."""
        from .driver import apply_edits_device
        return apply_edits_device(f_hat, idx, val)

    # -- on-device entropy codec (DESIGN.md §8) ------------------------
    def pack_codes(self, r: jnp.ndarray):
        """int32 residual codes -> chunked-bitplane stream
        ``(words, bits, n_words)`` via the per-chunk Pallas transpose
        kernel (bitwise identical to the jnp and host codecs)."""
        from ..kernels.pack import pack_codes_pallas
        return pack_codes_pallas(r, interpret=self._interpret())

    def unpack_codes(self, words, bits, shape: Tuple[int, ...]
                     ) -> jnp.ndarray:
        """Inverse of ``pack_codes`` via the Pallas unpack kernel."""
        from ..kernels.pack import unpack_codes_pallas
        return unpack_codes_pallas(words, bits, tuple(shape),
                                   interpret=self._interpret())

    def _tiled_step(self, g: jnp.ndarray, topo, tile: int):
        """pMSz-style block-decomposed iteration over the slab axis.

        Each tile [z0, z1) reads g with a 2-slab halo (the extrema masks
        of the 1-slab fix halo need g one slab further out), runs both
        kernels in global coordinates, and keeps only [z0, z1) of the
        result. Tiles all read the pre-iteration g, so the update stays
        the dense simultaneous one — bitwise equal to untiled.
        """
        from ..kernels.fixpass import fix_pass_pallas
        n = g.shape[0]
        interp = self._interpret()
        outs = []
        viol = jnp.int32(0)
        for z0 in range(0, n, tile):
            z1 = min(z0 + tile, n)
            a, b = max(z0 - 2, 0), min(z1 + 2, n)
            ext = slice(a, b)
            masks = self.extrema_masks(
                g[ext],
                type(topo)(topo.up_c[ext], topo.dn_c[ext],
                           topo.is_max[ext], topo.is_min[ext],
                           topo.M[ext], topo.m[ext], topo.lower[ext]),
                slab_lo=a, n_slabs_total=n)
            c, d = max(z0 - 1, 0), min(z1 + 1, n)
            ss = slice(c - a, d - a)
            g2, _, _ = fix_pass_pallas(
                g[c:d], topo.lower[c:d],
                masks.self_edit[ss], masks.demote_src[ss],
                masks.promote_src[ss], masks.up_c_g[ss], topo.dn_c[c:d],
                interpret=interp, slab_lo=c, n_slabs_total=n)
            outs.append(g2[z0 - c:z0 - c + (z1 - z0)])
            tp = slice(z0 - a, z1 - a)  # tile proper: each slab counted once
            viol = viol + (jnp.sum(masks.self_edit[tp])
                           + jnp.sum(masks.demote_src[tp])
                           + jnp.sum(masks.promote_src[tp])).astype(jnp.int32)
        return jnp.concatenate(outs, axis=0), viol

    # -- dirty-slab worklist loop (DESIGN.md §7) -----------------------
    def use_worklist(self, shape: Tuple[int, ...]) -> bool:
        """Whether a solo fix loop on ``shape`` should run through
        ``worklist_loop``. Explicit ``worklist=True/False`` wins; auto
        (None) engages above ``worklist_min_slabs`` slabs, where the
        per-group ``lax.cond`` overhead is small against the stencil
        work a converged group saves."""
        if len(shape) not in (2, 3):
            return False
        if self.worklist is not None:
            return bool(self.worklist) and shape[0] >= 2
        return shape[0] >= self.worklist_min_slabs

    def worklist_loop(self, g0: jnp.ndarray, topo, *, max_iters: int):
        """The fused fix loop with per-slab-group early exit: returns
        (g, iters, converged, skipped_slabs), the first three bitwise
        equal to the dense loop's.

        Iteration state carries the previous pass's per-slab fix-source
        and edit-target counts. A group of slabs re-runs the stencils iff
        any slab within 2 slabs of the group carried an edit target last
        iteration; other groups reuse their g slice (unchanged by
        construction) and their stale — still exact — source counts. The
        2-slab radius is the stencil dependency depth: a slab's fix
        output reads masks one slab out, and those masks read g one slab
        further (DESIGN.md §7 gives the induction). Convergence tests the
        summed source counts, identical to the dense loop's violation
        count, so iteration counts match too. ``skipped_slabs``
        accumulates slabs whose group was skipped, summed over
        iterations (the benchmark's worklist-win metric).

        The groups run as one ``lax.scan``: the slab axis is padded by
        two slabs below and two (plus the last group's shortfall) above,
        so every group reads a same-shaped window at a traced offset and
        the two kernels are compiled once, whatever the slab count. The
        padding lies outside the domain, where the kernels' global
        coordinates mask it.
        """
        from ..kernels.fixpass import fix_pass_pallas
        n = g0.shape[0]
        wg = max(int(self.worklist_group), 1)
        n_groups = -(-n // wg)
        n_pad = n_groups * wg
        interp = self._interpret()

        def pad(x):
            return jnp.pad(x, ((2, 2 + n_pad - n),) + ((0, 0),) * (x.ndim - 1))

        topo_p = jax.tree_util.tree_map(pad, topo)
        sizes = jnp.clip(n - wg * jnp.arange(n_groups, dtype=jnp.int32),
                         0, wg)

        def tile_step(g_p, gi):
            z0 = gi * wg                       # padded index of slab z0-2

            def win(x, lo, size):
                return jax.lax.dynamic_slice_in_dim(x, z0 + lo, size)

            masks = self.extrema_masks(
                win(g_p, 0, wg + 4),
                jax.tree_util.tree_map(lambda x: win(x, 0, wg + 4), topo_p),
                slab_lo=z0 - 2, n_slabs_total=n)
            ss = slice(1, wg + 3)
            g2, src, tgt = fix_pass_pallas(
                win(g_p, 1, wg + 2), win(topo_p.lower, 1, wg + 2),
                masks.self_edit[ss], masks.demote_src[ss],
                masks.promote_src[ss], masks.up_c_g[ss],
                win(topo_p.dn_c, 1, wg + 2),
                interpret=interp, slab_lo=z0 - 1, n_slabs_total=n)
            return g2[1:wg + 1], src[1:wg + 1], tgt[1:wg + 1]

        def body(state):
            g_p, it, src, tgt, skipped = state
            dirty = tgt > 0
            run_slab = dirty
            for s in (1, 2):        # dilate by the 2-slab stencil radius
                run_slab = (run_slab
                            | jnp.pad(dirty[s:], (0, s))
                            | jnp.pad(dirty[:-s], (s, 0)))
            run = jnp.pad(run_slab, (0, n_pad - n)).reshape(
                n_groups, wg).any(axis=1)
            src_p = jnp.pad(src, (0, n_pad - n))

            def group(g_next, xs):
                gi, run_g = xs

                def compute(g_next):
                    g2, s2, t2 = tile_step(g_p, gi)
                    return (jax.lax.dynamic_update_slice_in_dim(
                        g_next, g2, gi * wg + 2, 0), s2, t2)

                def reuse(g_next):
                    return (g_next,
                            jax.lax.dynamic_slice_in_dim(src_p, gi * wg, wg),
                            jnp.zeros(wg, jnp.int32))

                g_next, s2, t2 = jax.lax.cond(run_g, compute, reuse, g_next)
                return g_next, (s2, t2)

            g_next, (srcs, tgts) = jax.lax.scan(
                group, g_p, (jnp.arange(n_groups, dtype=jnp.int32), run))
            skipped = skipped + jnp.sum(jnp.where(run, 0, sizes))
            return (g_next, it + 1, srcs.reshape(-1)[:n],
                    tgts.reshape(-1)[:n], skipped)

        def cond(state):
            _, it, src, _, _ = state
            return (jnp.sum(src) > 0) & (it < max_iters)

        # first iteration unconditionally runs every group (tgt
        # sentinel 1s), mirroring the dense loop's step-then-while shape
        state0 = (pad(g0), jnp.int32(0), jnp.zeros(n, jnp.int32),
                  jnp.ones(n, jnp.int32), jnp.int32(0))
        g_p, it, src, tgt, skipped = jax.lax.while_loop(cond, body,
                                                        body(state0))
        return g_p[2:2 + n], it, jnp.sum(src) == 0, skipped


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

BackendLike = Union[str, ReferenceBackend, PallasBackend]

_REGISTRY: Dict[str, object] = {}

# backends living in higher layers register themselves on import; naming
# one here pulls its module in on demand so `get_backend("sharded")` works
# without the caller importing repro.distributed first
_LAZY_MODULES: Dict[str, str] = {"sharded": "repro.distributed.shardfix"}


def register_backend(backend, name: Optional[str] = None) -> None:
    """Register a backend instance under ``name`` (default: backend.name)."""
    _REGISTRY[name or backend.name] = backend


def _ensure_lazy_backends() -> None:
    import importlib
    for name, module in _LAZY_MODULES.items():
        if name not in _REGISTRY:
            importlib.import_module(module)


def available_backends() -> Tuple[str, ...]:
    """Sorted names of every registered stencil backend (lazy
    higher-layer backends are imported first so the list is total)."""
    _ensure_lazy_backends()
    return tuple(sorted(_REGISTRY))


def get_backend(spec: BackendLike):
    """Resolve a backend name or pass an instance through."""
    if isinstance(spec, str):
        if spec == "auto":
            raise ValueError(
                "'auto' needs field shape/dtype — use resolve_backend()")
        if spec not in _REGISTRY and spec in _LAZY_MODULES:
            _ensure_lazy_backends()
        try:
            return _REGISTRY[spec]
        except KeyError:
            raise ValueError(
                f"unknown stencil backend {spec!r}; "
                f"available: {available_backends()}") from None
    if not hasattr(spec, "fused_step"):
        raise TypeError(f"not a stencil backend: {spec!r}")
    return spec


def _auto_sharded(shape, dtype, mesh):
    """The 'sharded' backend bound to ``mesh`` when it (or the active
    ``with mesh:`` context) has >= 2 data-axis devices, else None."""
    be = get_backend("sharded")          # lazy-registers via _LAZY_MODULES
    if mesh is not None:
        be = be.with_mesh(mesh)
    else:
        try:
            be = be.bind()               # resolve the active mesh context
        except ValueError:
            return None
    if be.n_data_devices() < 2 or not be.supports(shape, dtype):
        return None
    return be


def resolve_backend(spec: BackendLike, shape: Tuple[int, ...], dtype,
                    mesh=None):
    """Like get_backend, but 'auto' picks the best supported backend —
    'sharded' when a mesh with >= 2 data-axis devices is given or active,
    else 'pallas', else 'reference'. An explicitly named backend raises on
    unsupported inputs instead of silently falling back; ``mesh`` is bound
    into a mesh-less sharded backend when provided."""
    if isinstance(spec, str) and spec == "auto":
        be = _auto_sharded(shape, dtype, mesh)
        if be is not None:
            return be
        be = _REGISTRY["pallas"]
        if be.supports(shape, dtype):
            return be
        return _REGISTRY["reference"]
    be = get_backend(spec)
    if mesh is not None and hasattr(be, "with_mesh") \
            and getattr(be, "mesh", None) is None:
        be = be.with_mesh(mesh)
    if not be.supports(shape, dtype):
        if hasattr(be, "bind"):
            be.bind()   # raises the 'needs a mesh' error when that is why
        raise ValueError(
            f"backend {be.name!r} does not support fields of shape {shape} "
            f"dtype {dtype}; use backend='auto' for automatic fallback")
    return be


register_backend(ReferenceBackend())
register_backend(PallasBackend())
# small fixed tile: exercises the halo-exchange path on modest fields
register_backend(PallasBackend(name="pallas_tiled", z_tile=8))
# worklist always on with small groups: exercises the dirty-slab loop
# (and its skip path) on modest fields
register_backend(PallasBackend(name="pallas_worklist", worklist=True,
                               worklist_group=4))
