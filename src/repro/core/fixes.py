"""False-point detection and decreasing-edit fix passes.

Two execution modes:

* ``paper``  — faithful reproduction of the paper's workflow (Fig. 3):
  C-loops run the four sub-loops (FPmax, FPmin, FNmax, FNmin) sequentially
  to their individual fixpoints, then an R-pass computes the full MSS of the
  current edited field (pointer jumping), identifies troublemakers as the
  first label discrepancy along integral lines, and reroutes them; C- and
  R-loops alternate until convergence (Section 5.3).

* ``fused``  — our beyond-paper TPU formulation: all six fix conditions are
  *local stencil predicates*, applied simultaneously in one dense pass per
  iteration. The R-condition uses the local characterization
      troublemaker(t)  <=>  M_f[dir_up_g(t)] != M_f[t]   (t non-max)
  which avoids recomputing MSS labels inside the loop entirely (labels are
  only needed once on f, and once at the end for verification). All edits
  remain monotonically decreasing, so the paper's convergence argument
  (Lemma 1) applies verbatim.

The fused iteration itself is executed by a pluggable *stencil backend*
(``repro.core.backend``): ``reference`` (dense jnp, XLA-fused) or
``pallas`` (slab-decomposed TPU kernels, with Z-tiling for large fields).
Backends are bitwise-interchangeable; ``"auto"`` prefers pallas. The
stencil predicates themselves (false_critical_masks, trouble_masks, the
pull-based edit rule) live in backend.py and are re-exported here.

Conflict resolution: the paper uses atomicCAS keeping the most significant
edit. All edits decrease, and the edit value ``(g+f-xi)/2`` depends only on
the *target* vertex, so concurrent edits to one vertex are identical — the
dense formulation (each vertex pulls edit requests from its stencil) is
conflict-free by construction and bitwise deterministic (DESIGN.md §2).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import grid
from .backend import (BackendLike, FalseMasks, StencilMasks,  # noqa: F401
                      _halve_toward_lower, _pull, _device_scalar,
                      false_critical_masks, get_backend, keep_going,
                      resolve_backend, trouble_masks)
from .labels import labels_from_codes, pointer_jump


#: what a caller raises when a fix loop ends unconverged (DESIGN.md §2)
NOT_CONVERGED = ("MSz fix loops did not converge: a pass with violations "
                 "moved no value, or max_iters was reached")


class FieldTopo(NamedTuple):
    """Static per-field topology of the ORIGINAL data (computed once)."""
    up_c: jnp.ndarray      # steepest ascending dir codes of f
    dn_c: jnp.ndarray      # steepest descending dir codes of f
    is_max: jnp.ndarray    # bool
    is_min: jnp.ndarray    # bool
    M: jnp.ndarray         # ascending (max) labels of f, int32, f.shape
    m: jnp.ndarray         # descending (min) labels of f
    lower: jnp.ndarray     # f - xi  (edit lower bound, Eq. 1)


def field_topology(f: jnp.ndarray, xi) -> FieldTopo:
    """Precompute everything the fix loops need from the ORIGINAL
    field: steepest direction codes, extremum masks, ascending/
    descending MSS labels, and the per-vertex lower bound f - xi.

    Runs eagerly, so the two host scalars it consumes (the self code and
    ``xi``) cross via the explicit transfer seam — an implicit eager
    promotion would trip ``debug.no_transfers()`` on every call."""
    up_c, dn_c = grid.steepest_dirs(f)
    M, m = labels_from_codes(up_c, dn_c)
    sc = _device_scalar(grid.self_code(f.ndim), up_c.dtype)
    return FieldTopo(up_c, dn_c, up_c == sc, dn_c == sc, M, m,
                     f - _device_scalar(xi, f.dtype))


# ---------------------------------------------------------------------------
# fused mode — one dense pass applies every fix class at once, dispatched
# to a stencil backend
# ---------------------------------------------------------------------------

def fused_pass(g: jnp.ndarray, topo: FieldTopo,
               backend: BackendLike = "reference"):
    """One iteration of the fused fixed-point loop.

    Returns (g_next, n_violations). n_violations == 0 iff g already
    preserves the full MS segmentation of f (extrema + all labels).
    """
    return get_backend(backend).fused_step(g, topo)[:2]


def _bind(be):
    """Freeze call-time context (the active mesh, for the sharded backend)
    into the instance so jit caches key on it."""
    return be.bind() if hasattr(be, "bind") else be


def _moved(g2, g) -> jnp.ndarray:
    """How many values a paper-mode pass moved (int32). The fused loops
    take the count from ``fused_step``."""
    return jnp.sum(g2 != g, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("max_iters", "backend"))
def _fused_fix_impl(g0: jnp.ndarray, topo: FieldTopo,
                    max_iters: Optional[int], backend):
    be = backend
    if hasattr(be, "fix_loop"):
        # distributed backends run the whole loop inside one shard_map
        # (topology halos exchanged once); trajectory is bitwise equal
        return be.fix_loop(g0, topo, max_iters=max_iters)
    if hasattr(be, "worklist_loop") and be.use_worklist(g0.shape):
        # dirty-slab worklist: re-runs the stencils only near last
        # iteration's edit targets; bitwise equal to the dense loop
        g, iters, ok, _ = be.worklist_loop(g0, topo, max_iters=max_iters)
        return g, iters, ok

    def cond(state):
        g, it, viol, moved = state
        return keep_going(viol, moved, it, max_iters)

    def body(state):
        g, it, _, _ = state
        g2, viol2, moved2 = be.fused_step(g, topo)
        return g2, it + 1, viol2, moved2

    g1, viol1, moved1 = be.fused_step(g0, topo)
    g, iters, viol, _ = jax.lax.while_loop(
        cond, body, (g1, jnp.int32(1), viol1, moved1))
    return g, iters, viol == 0


def fused_fix(g0: jnp.ndarray, topo: FieldTopo,
              max_iters: Optional[int] = None,
              backend: BackendLike = "auto", mesh=None):
    """Run the fused loop to convergence. Returns (g, iters, converged).

    The loop ends when a pass finds no violation (converged) or when a
    pass with violations moves no value (not converged; DESIGN.md §2
    says when that can happen). ``max_iters`` None sets no cap on the
    passes; a number caps them too.

    ``backend`` selects the stencil execution strategy (see
    core.backend); all backends produce bitwise-identical trajectories,
    so this choice affects speed only. ``mesh`` routes the loop through
    the slab-sharded SPMD backend (repro.distributed.shardfix) when it
    has >= 2 ``data``-axis devices and ``backend`` is "auto"/"sharded".
    """
    be = _bind(resolve_backend(backend, g0.shape, g0.dtype, mesh=mesh))
    return _fused_fix_impl(g0, topo, max_iters=max_iters, backend=be)


@functools.partial(jax.jit, static_argnames=("max_iters", "backend"))
def _worklist_fix_impl(g0: jnp.ndarray, topo: FieldTopo,
                       max_iters: Optional[int], backend):
    return backend.worklist_loop(g0, topo, max_iters=max_iters)


def fused_fix_worklist(g0: jnp.ndarray, topo: FieldTopo,
                       max_iters: Optional[int] = None,
                       backend: BackendLike = "pallas_worklist", mesh=None):
    """Run the fused loop through a backend's dirty-slab worklist driver
    (DESIGN.md §7), regardless of its auto-engage threshold. Returns
    (g, iters, converged, skipped_slabs) with the first three bitwise
    equal to ``fused_fix``; ``skipped_slabs`` counts slabs whose group
    was skipped, summed over iterations — the worklist's win metric,
    nonzero whenever violations stay localized for an iteration or more.
    """
    be = _bind(resolve_backend(backend, g0.shape, g0.dtype, mesh=mesh))
    if not hasattr(be, "worklist_loop"):
        raise ValueError(
            f"backend {be.name!r} has no dirty-slab worklist driver; "
            "use the pallas backend family")
    return _worklist_fix_impl(g0, topo, max_iters=max_iters, backend=be)


def _batched_loop(step, g, topo, it0, iters_b, viol, moved, n_iters):
    """The vmapped fused loop: each member steps while ``keep_going``
    holds for it, to at most ``n_iters`` passes of the loop (None: no
    cap). A member that stops is frozen (its fix-target set is empty, or
    nothing moved, so ``g2 == g`` for it already; the ``where`` is
    belt-and-braces), so its (g, iters) stay bitwise equal to a solo
    run's. Returns (g, iters_b, viol, moved)."""
    def cond(state):
        _, it, _, viol, moved = state
        return jnp.any(keep_going(viol, moved, it, n_iters))

    def body(state):
        g, it, iters_b, viol, moved = state
        g2, viol2, moved2 = step(g, topo)
        active = keep_going(viol, moved, it, n_iters)
        keep = active.reshape((-1,) + (1,) * (g.ndim - 1))
        return (jnp.where(keep, g2, g), it + 1,
                iters_b + active.astype(jnp.int32),
                jnp.where(active, viol2, viol),
                jnp.where(active, moved2, moved))

    g, _, iters_b, viol, moved = jax.lax.while_loop(
        cond, body, (g, it0, iters_b, viol, moved))
    return g, iters_b, viol, moved


@functools.partial(jax.jit, static_argnames=("max_iters", "backend"))
def _fused_fix_batch_impl(g0: jnp.ndarray, topo: FieldTopo,
                          max_iters: Optional[int], backend):
    step = jax.vmap(backend.fused_step, in_axes=(0, 0))
    g1, viol1, moved1 = step(g0, topo)
    iters0 = jnp.ones(g0.shape[0], jnp.int32)
    g, iters_b, viol, _ = _batched_loop(step, g1, topo, jnp.int32(1), iters0,
                                        viol1, moved1, max_iters)
    return g, iters_b, viol == 0


def _pow2_at_least(n: int) -> int:
    """Smallest power of two >= n (compaction bucket sizes; twin of
    compress.pipeline's helper, duplicated to keep core below compress)."""
    return 1 << max(int(n) - 1, 0).bit_length()


@functools.partial(jax.jit, static_argnames=("k", "backend"))
def _fused_fix_round_impl(g0: jnp.ndarray, topo: FieldTopo,
                          viol0: jnp.ndarray, moved0: jnp.ndarray, k: int,
                          backend):
    """Up to ``k`` iterations of the vmapped fused loop on one compaction
    bucket. ``viol0`` / ``moved0`` are each member's violation and moved
    counts entering the round (the first round passes 1-sentinels so
    every member takes the dense loop's unconditional first step);
    members that stop freeze, exactly as in ``_fused_fix_batch_impl``, so
    per-member trajectories stay bitwise equal to solo runs. Returns (g,
    iters_this_round, viol, moved)."""
    step = jax.vmap(backend.fused_step, in_axes=(0, 0))
    iters0 = jnp.zeros(g0.shape[0], jnp.int32)
    return _batched_loop(step, g0, topo, jnp.int32(0), iters0, viol0,
                         moved0, k)


def _fused_fix_batch_compact(g0: jnp.ndarray, topo: FieldTopo,
                             max_iters: Optional[int], be, every: int):
    """Active-member compaction driver: the batched loop in host-driven
    rounds of ``every`` iterations, with still-active members gathered
    into a dense prefix between rounds so converged members stop costing
    vmap lanes. Buckets are padded to power-of-two sizes (repeating an
    active member; its result is discarded) so jit specializes on
    ~log2(B) bucket shapes, not one per occupancy. Per-member results are
    bitwise equal to ``_fused_fix_batch_impl``'s: gather/scatter move
    exact copies, the vmapped step is elementwise per member, and every
    member still in a bucket has run exactly the global iteration count.
    """
    B = g0.shape[0]
    g = g0
    viol = np.ones(B, np.int32)        # 1-sentinels: everyone steps once
    moved = np.ones(B, np.int32)
    iters = np.zeros(B, np.int32)
    active = np.arange(B)
    it_done = 0
    while active.size and (max_iters is None or it_done < max_iters):
        k = every if max_iters is None else min(every, max_iters - it_done)
        n = active.size
        cap = _pow2_at_least(n)
        # gather padding repeats active[0]; the scatter-back pads with B
        # (out of bounds, mode="drop") so only the n real lanes land —
        # a host-side [:n] slice would be an implicit transfer per round
        sel = np.concatenate([active, np.full(cap - n, active[0],
                                              active.dtype)])
        scat = np.concatenate([active, np.full(cap - n, B, active.dtype)])
        pad0 = np.zeros(cap - n, np.int32)
        viol_a0 = np.concatenate([viol[active], pad0])
        moved_a0 = np.concatenate([moved[active], pad0])
        g, dit_a, viol_a, moved_a = _compact_round(
            g, topo, jax.device_put(sel), jax.device_put(scat),
            jax.device_put(viol_a0), jax.device_put(moved_a0), k=k,
            backend=be)
        # host sync: one small explicit pull per round (cap-padded)
        dit = jax.device_get(dit_a)[:n]
        viol_n = jax.device_get(viol_a)[:n]
        moved_n = jax.device_get(moved_a)[:n]
        # mszlint: disable=scatter-discipline -- active is a flatnonzero
        # subset, unique by construction
        iters[active] += dit
        viol[active] = viol_n
        moved[active] = moved_n
        it_done += k
        active = active[(viol_n > 0) & (moved_n > 0)]
    return g, jax.device_put(iters), jax.device_put(viol == 0)


@functools.partial(jax.jit, static_argnames=("k", "backend"))
def _compact_round(g: jnp.ndarray, topo: FieldTopo, sel: jnp.ndarray,
                   scat: jnp.ndarray, viol_a: jnp.ndarray,
                   moved_a: jnp.ndarray, k: int, backend):
    """One compaction round, fully jitted: gather the padded active
    bucket, run up to ``k`` iterations, scatter results back (padding
    lanes carry out-of-bounds indices and drop). Keeping the gather/
    scatter inside jit bakes every index constant in at trace time —
    eager ``take``/``at[].set`` would ship scalars per call, tripping
    ``debug.no_transfers()``."""
    g_a = jnp.take(g, sel, axis=0)
    topo_a = jax.tree_util.tree_map(lambda x: jnp.take(x, sel, axis=0), topo)
    g_a, dit_a, viol_a, moved_a = _fused_fix_round_impl(
        g_a, topo_a, viol_a, moved_a, k=k, backend=backend)
    return g.at[scat].set(g_a, mode="drop"), dit_a, viol_a, moved_a


def fused_fix_batch(g0: jnp.ndarray, topo: FieldTopo,
                    max_iters: Optional[int] = None,
                    backend: BackendLike = "auto", mesh=None,
                    batching: str = "auto", compact_every: int = 8):
    """Batched fused loop over a leading batch axis (many-field workloads:
    timestep series, ensemble members).

    ``g0``: (B, *spatial); every FieldTopo leaf carries the same leading
    batch axis. The per-iteration pass is vmapped across the batch and
    members that converge early stop costing work, so each member's
    (g, iters) is bitwise identical to a solo ``fused_fix`` run. Returns
    (g (B, *spatial), iters (B,), converged (B,) bool).

    ``batching`` picks the early-exit mechanism — the choice never
    changes results, only cost:

    * ``"compact"`` — active-member compaction (DESIGN.md §7): every
      ``compact_every`` iterations the still-active members are gathered
      into a power-of-two bucket and only that bucket runs the next
      round, so batch cost approaches sum(iters) instead of
      B x max(iters).
    * ``"fused"`` — the legacy single vmapped while_loop: converged
      members are frozen by a ``where`` but still occupy vmap lanes
      until the slowest member converges.
    * ``"auto"`` — compaction for B > 1, the plain loop for B == 1
      (a single member has nothing to compact away).

    With a sharded backend (``mesh`` with >= 2 data-axis devices, or
    backend="sharded") the members run sequentially through the mesh —
    each member still bitwise equal to its solo run; vmap over shard_map
    is not attempted and ``batching`` is ignored.
    """
    if batching not in ("auto", "compact", "fused"):
        raise ValueError(
            'batching must be "auto", "compact", or "fused"; '
            f"got {batching!r}")
    if compact_every < 1:
        raise ValueError(f"compact_every must be >= 1, got {compact_every}")
    be = _bind(resolve_backend(backend, g0.shape[1:], g0.dtype, mesh=mesh))
    if hasattr(be, "fix_loop"):
        outs = [_fused_fix_impl(g0[i],
                                jax.tree_util.tree_map(lambda x: x[i], topo),
                                max_iters=max_iters, backend=be)
                for i in range(g0.shape[0])]
        return (jnp.stack([g for g, _, _ in outs]),
                jnp.stack([it for _, it, _ in outs]),
                jnp.stack([ok for _, _, ok in outs]))
    if batching == "auto":
        batching = "compact" if g0.shape[0] > 1 else "fused"
    if batching == "compact":
        return _fused_fix_batch_compact(jax.device_put(g0), topo, max_iters,
                                        be, compact_every)
    return _fused_fix_batch_impl(g0, topo, max_iters=max_iters, backend=be)


# ---------------------------------------------------------------------------
# paper mode — sequential sub-loops, label recomputation in R-loops
# ---------------------------------------------------------------------------

def _subloop(g, topo, which: str, max_iters):
    """Run one false-critical-point class to its fixpoint (Section 5.1)."""
    def masks(g):
        fm = false_critical_masks(g, topo)
        return fm

    def target_of(fm):
        if which == "fpmax":      # Eq. 2: decrease the vertex itself
            return fm.fpmax
        if which == "fnmin":      # Eq. 5: decrease the vertex itself
            return fm.fnmin
        if which == "fpmin":
            # DEVIATION from Eq. 3 as printed ("decrease the maximal
            # neighbor"): that target can pin at its lower bound while
            # still above g_i (e.g. neighbors j: f_j >> f_i and k:
            # f_k < f_i — the fix never touches k), deadlocking the
            # sub-loop. We decrease the ORIGINAL steepest-descending
            # neighbor dir_dn_f(i) instead: f_c - xi < f_i - xi <= g_i
            # guarantees it eventually undercuts g_i. See DESIGN.md §2.
            return _pull(fm.fpmin, topo.dn_c)
        if which == "fnmax":      # Eq. 4: decrease i's maximal (g) neighbor
            return _pull(fm.fnmax, fm.up_c_g)
        raise ValueError(which)

    count_of = dict(fpmax=lambda fm: fm.fpmax, fnmin=lambda fm: fm.fnmin,
                    fpmin=lambda fm: fm.fpmin, fnmax=lambda fm: fm.fnmax)[which]

    def cond(state):
        g, it, n, moved = state
        return keep_going(n, moved, it, max_iters)

    def body(state):
        g, it, _, _ = state
        fm = masks(g)
        g2 = _halve_toward_lower(g, topo.lower, target_of(fm))
        fm2 = masks(g2)
        return (g2, it + 1, jnp.sum(count_of(fm2)).astype(jnp.int32),
                _moved(g2, g))

    fm0 = masks(g)
    n0 = jnp.sum(count_of(fm0)).astype(jnp.int32)
    g, it, _, _ = jax.lax.while_loop(cond, body,
                                     (g, jnp.int32(0), n0, jnp.int32(1)))
    return g, it


def _c_loop(g, topo, max_iters):
    """One C-loop: the four sub-loops in the paper's order, repeated until
    no false critical point remains."""
    def n_false(g):
        fm = false_critical_masks(g, topo)
        return (jnp.sum(fm.fpmax) + jnp.sum(fm.fpmin)
                + jnp.sum(fm.fnmax) + jnp.sum(fm.fnmin)).astype(jnp.int32)

    def cond(state):
        g, it, n, moved = state
        return keep_going(n, moved, it, max_iters)

    def body(state):
        g0, it, _, _ = state
        g = g0
        for which in ("fpmax", "fpmin", "fnmax", "fnmin"):
            g, _ = _subloop(g, topo, which, max_iters)
        return g, it + 1, n_false(g), _moved(g, g0)

    g, it, _, _ = jax.lax.while_loop(
        cond, body, (g, jnp.int32(0), n_false(g), jnp.int32(1)))
    return g


def _r_pass(g, topo):
    """One R-pass (Section 5.2): recompute the MSS of g (the expensive
    pointer-jumping step the paper parallelizes), find falsely labeled
    regular points, locate troublemakers, reroute with one edit each."""
    fm = false_critical_masks(g, topo)
    Mg, mg = labels_from_codes(fm.up_c_g, fm.dn_c_g)
    wrong_max_lab = Mg != topo.M
    wrong_min_lab = mg != topo.m
    t_max, t_min = trouble_masks(fm, topo)
    # paper: troublemaker = FIRST discrepancy along a falsely-labeled
    # vertex's integral line == locally-diverging AND itself falsely labeled.
    t_max = t_max & wrong_max_lab
    t_min = t_min & wrong_min_lab
    target = _pull(t_max, fm.up_c_g) | _pull(t_min, topo.dn_c)
    g2 = _halve_toward_lower(g, topo.lower, target)
    n_wrong = (jnp.sum(wrong_max_lab) + jnp.sum(wrong_min_lab)).astype(jnp.int32)
    return g2, n_wrong


@functools.partial(jax.jit, static_argnames=("max_iters",))
def paper_fix(g0: jnp.ndarray, topo: FieldTopo,
              max_iters: Optional[int] = None):
    """Alternate C- and R-loops until no false critical/regular point
    (Section 5.3). Returns (g, outer_iters, converged). Every loop stops
    as the fused one does (``keep_going``)."""
    def cond(state):
        g, it, n, moved = state
        return keep_going(n, moved, it, max_iters)

    def body(state):
        g_in, it, _, _ = state
        g = _c_loop(g_in, topo, max_iters)
        g, n_wrong = _r_pass(g, topo)
        fm = false_critical_masks(g, topo)
        n = (n_wrong + jnp.sum(fm.fpmax) + jnp.sum(fm.fpmin)
             + jnp.sum(fm.fnmax) + jnp.sum(fm.fnmin)).astype(jnp.int32)
        return g, it + 1, n, _moved(g, g_in)

    g, iters, n, _ = jax.lax.while_loop(
        cond, body, (g0, jnp.int32(0), jnp.int32(1), jnp.int32(1)))
    return g, iters, n == 0
