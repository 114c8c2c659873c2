"""Morse-Smale segmentation labels via parallel path compression.

Implements the pointer-doubling ('path compression' / pointer jumping)
MSS computation of Maack et al. used by the paper (Section 6.2): every
vertex stores the next vertex of its ascending (descending) integral line;
iterating ``nxt <- nxt[nxt]`` halves every path length per step, so the
label array converges in O(log(longest integral line)) gather sweeps.

The GPU lock-free worklist of the paper is replaced by dense fixpoint
iteration — on a vector machine the 'worklist' is simply the set of lanes
that still change, and the while_loop exits when none do.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import grid


def default_pointer_iters(n_vertices: int) -> int:
    """Doubling sweeps provably sufficient for any pointer chain over
    ``n_vertices``: an integral line visits each vertex at most once, so
    path lengths are < V, each sweep doubles the resolved hop distance,
    and ceil(log2(V)) sweeps reach every root; +1 lets the convergence
    check observe the fixed point. With this bound the while_loop can
    only exit converged — there is no silent truncation."""
    return max(math.ceil(math.log2(max(int(n_vertices), 2))), 1) + 1


def pointer_jump(nxt: jnp.ndarray,
                 max_iters: Optional[int] = None) -> jnp.ndarray:
    """Resolve next-pointers to root labels by pointer doubling.

    nxt: int32 [V], extrema are self-pointers (fixed points).
    Returns int32 [V]: the root (extremum) linear index for every vertex.

    ``max_iters=None`` (default) derives the sweep bound from the field
    size (``default_pointer_iters``), which guarantees convergence for
    every possible pointer field — including a single integral line
    snaking through all V vertices. Passing an explicit smaller bound is
    best-effort only: the loop then exits at the bound with unresolved
    labels and no error (the convergence check is part of the loop
    condition, not an output).
    """
    if max_iters is None:
        max_iters = default_pointer_iters(nxt.size)

    # the state carries the next sweep's pointers beside the current
    # ones, so each sweep gathers once: the test for a change and the
    # step share one gather
    def cond(state):
        it, cur, nxt2 = state
        return (it < max_iters) & jnp.any(cur != nxt2)

    def body(state):
        it, _, nxt2 = state
        return it + 1, nxt2, jnp.take(nxt2, nxt2)

    _, out, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), nxt, jnp.take(nxt, nxt)))
    return out


@jax.jit
def mss_labels(f: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(max_label M, min_label m) per vertex — the full PLMSS of ``f``.

    ``M[v]`` is the linear index of the maximum reached by v's ascending
    integral line; ``m[v]`` the minimum reached descending. The MS
    segmentation of the paper is the pair ``<m, M>``.
    """
    up_c, dn_c = grid.steepest_dirs(f)
    M = pointer_jump(grid.dir_to_pointer(up_c)).reshape(f.shape)
    m = pointer_jump(grid.dir_to_pointer(dn_c)).reshape(f.shape)
    return M, m


@jax.jit
def labels_from_codes(up_c: jnp.ndarray, dn_c: jnp.ndarray):
    M = pointer_jump(grid.dir_to_pointer(up_c)).reshape(up_c.shape)
    m = pointer_jump(grid.dir_to_pointer(dn_c)).reshape(dn_c.shape)
    return M, m


def segmentation_accuracy(f: jnp.ndarray, g: jnp.ndarray) -> jnp.ndarray:
    """'Right labeled ratio' of the paper (Eq. 9): fraction of vertices whose
    <min,max> label pair matches between f and g."""
    Mf, mf = mss_labels(f)
    Mg, mg = mss_labels(g)
    right = (Mf == Mg) & (mf == mg)
    return jnp.mean(right.astype(jnp.float32))
