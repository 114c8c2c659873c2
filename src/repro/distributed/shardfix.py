"""Sharded pMSz fix loop over the device mesh (shard_map + ppermute).

PR 1 made the Pallas fix kernels the single-device production path,
including sequential Z-tiling with per-iteration halo re-exchange; PR 4
generalized that tiling into 1D SPMD Z-slab chains. This module carries
the decomposition the rest of the way (DESIGN.md §9): fields shard over
true 2D/3D *block* meshes — ``('data_y','data_z')``, optionally
``('data_x','data_y','data_z')`` — with per-block halo exchange on every
sharded mesh axis and compute/communication overlap, pMSz's block
decomposition with overlapped ghost exchange (arXiv 2601.01787).

Axis naming: mesh axis ``data_z`` shards field axis 0 (the kernel slab
axis), ``data_y`` field axis 1, ``data_x`` field axis 2 (3D only; a
size->1 ``data_x`` axis is ignored for 2D fields). The legacy 1-axis
``data`` name keeps meaning "shard field axis 0" — every PR-4 caller and
test runs unchanged, bit for bit.

Halo-exchange protocol per fused iteration (overlap OFF — the legacy
schedule, generalized to N axes):

  1. exchange 1-deep ``g`` faces along every sharded axis IN ORDER —
     later axes exchange faces of the already-extended block, so edge
     and corner ghosts of the 26-stencil arrive transitively without
     dedicated diagonal sends (the two-phase face exchange; §9 has the
     correctness argument);
  2. run the extrema/false-point kernel on the extended block in GLOBAL
     coordinates (traced per-axis origins ``axis_index * L - 1``, static
     real extents) — its interior is exact;
  3. exchange 1-deep faces of the fresh interior masks the same way
     (one stacked exchange for all four mask arrays per axis);
  4. run the fix kernel on the extended block and keep its interior;
  5. count fix sources over interior real vertices only and ``psum``
     over every sharded axis — the loop's convergence predicate,
     identical on every device.

With overlap ON (default for block meshes with blocks >= 3 vertices per
sharded axis), the iteration is split into an *interior pass* with no
halo dependency — issued while a single 2-deep ``g`` face exchange is in
flight — and a *boundary pass* that consumes the fresh ghosts: with
2-deep ``g`` ghosts every device recomputes its boundary-shell masks
(including the ghost ring) locally, so the mid-iteration mask exchange
disappears entirely and the schedule has exactly one collective phase
per iteration for the XLA scheduler to overlap with the interior
kernels. Both schedules produce bitwise-identical trajectories — fields,
violation counts, iteration counts (tests/test_blockfix.py sweeps both
against ``reference``).

Padding, worklists, and the rest of the PR-4/PR-6 contract generalize
per block: non-divisible extents zero-pad at the high end of each
sharded axis (kernels mask true domain boundaries in global coordinates,
so pad and chain-end ppermute zeros never reach a real vertex), and the
per-device dirty worklist skips both kernels on blocks whose 2-vertex
dependency radius saw no ``g`` change last iteration, with dirt flags
folded axis-by-axis so diagonal-neighbor dirt propagates through the
same two-phase relay as the halos.

``ShardedBackend`` plugs this into the stencil-backend registry
(``repro.core.backend``) under the name ``"sharded"``; ``resolve_backend
("auto", ...)`` selects it automatically whenever a mesh with >= 2
devices on recognized data axes is active (``with mesh:``) or passed
explicitly.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from ..core.backend import keep_going, register_backend
from ..kernels.extrema import (default_interpret, extrema_masks_pallas,
                               typed_operand)
from ..kernels.fixpass import fix_pass_pallas
from ..kernels.lorenzo import lorenzo_quant_pallas

DATA_AXIS = "data"
#: block-mesh axis names, by the FIELD axis they shard: data_z -> axis 0
#: (the kernel slab axis), data_y -> axis 1, data_x -> axis 2.
BLOCK_AXES = ("data_z", "data_y", "data_x")
#: every mesh axis name the sharded backend recognizes as a data axis.
ALL_DATA_AXES = (DATA_AXIS,) + BLOCK_AXES


# ---------------------------------------------------------------------------
# mesh discovery
# ---------------------------------------------------------------------------

def active_data_mesh(axis_name: Optional[str] = None) -> Optional[Mesh]:
    """The mesh installed by ``with mesh:`` if it has ``axis_name`` (or,
    when None, any recognized data axis — ``data``/``data_z``/``data_y``/
    ``data_x``), else None. This is what makes ``backend="auto"``
    mesh-aware."""
    from jax._src import mesh as mesh_lib
    m = mesh_lib.thread_resources.env.physical_mesh
    if m.empty:
        return None
    names = (axis_name,) if axis_name is not None else ALL_DATA_AXES
    if not any(n in m.axis_names for n in names):
        return None
    return m


def data_axis_size(mesh: Optional[Mesh],
                   axis_name: Optional[str] = None) -> int:
    """Devices on ``axis_name`` (or, when None, the product over every
    recognized data axis present); 0 when the mesh is absent or has no
    such axis."""
    if mesh is None:
        return 0
    names = (axis_name,) if axis_name is not None else ALL_DATA_AXES
    present = [n for n in names if n in mesh.axis_names]
    if not present:
        return 0
    size = 1
    for n in present:
        size *= int(mesh.shape[n])
    return size


# ---------------------------------------------------------------------------
# block decomposition plan
# ---------------------------------------------------------------------------

class BlockAxis(NamedTuple):
    """One sharded field axis of a block plan: field axis ``dim`` splits
    into ``n`` blocks of (padded) extent ``L`` over mesh axis ``name``."""
    dim: int
    name: str
    n: int
    L: int


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """How a field decomposes over a mesh's data axes.

    ``names`` maps each field axis to its mesh axis name (None when
    unsharded; size-1 mesh axes keep their name for placement but emit
    no collectives); ``sharded`` lists the axes with >= 2 devices, in
    field-axis order — the canonical two-phase exchange order.
    """
    shape: Tuple[int, ...]
    names: Tuple[Optional[str], ...]
    sharded: Tuple[BlockAxis, ...]
    legacy: bool

    @property
    def ndim(self) -> int:
        """Field rank (2 or 3)."""
        return len(self.shape)

    def spec(self) -> PartitionSpec:
        """The PartitionSpec placing a field-shaped array on the mesh."""
        return PartitionSpec(*self.names)

    def padded_shape(self) -> Tuple[int, ...]:
        """Field shape after padding every sharded axis to ``n * L``."""
        out = list(self.shape)
        for a in self.sharded:
            out[a.dim] = a.n * a.L
        return tuple(out)

    def block_shape(self) -> Tuple[int, ...]:
        """Per-device local block shape (padded extents)."""
        out = list(self.shape)
        for a in self.sharded:
            out[a.dim] = a.L
        return tuple(out)

    def axis_names(self) -> Tuple[str, ...]:
        """Mesh axis names of the sharded axes (psum/ppermute targets)."""
        return tuple(a.name for a in self.sharded)

    def min_block(self) -> int:
        """Smallest sharded block extent (large sentinel when unsharded)."""
        return min([a.L for a in self.sharded], default=1 << 30)


def plan_blocks(shape: Sequence[int], mesh: Mesh,
                axis_name: Optional[str] = None) -> BlockPlan:
    """Build the :class:`BlockPlan` for a field ``shape`` on ``mesh``.

    ``axis_name`` forces the legacy single-axis decomposition over that
    mesh axis (field axis 0). Otherwise the plan maps ``data`` -> field
    axis 0 (legacy), or the block axes ``data_z``/``data_y``/``data_x``
    -> field axes 0/1/2; mixing ``data`` with block axes is an error, as
    is a >1-device ``data_x`` axis with a 2D field.
    """
    # mszlint: disable=transfer-discipline -- host planning over a shape tuple
    shape = tuple(int(s) for s in shape)
    ndim = len(shape)
    if ndim not in (2, 3):
        raise ValueError(f"block decomposition supports 2D/3D, got {shape}")
    names_map: Dict[int, str] = {}
    legacy = True
    if axis_name is not None:
        if axis_name not in mesh.axis_names:
            raise ValueError(
                f"mesh {mesh} has no {axis_name!r} axis to shard over")
        names_map[0] = axis_name
    else:
        block_present = [n for n in BLOCK_AXES if n in mesh.axis_names]
        if DATA_AXIS in mesh.axis_names:
            if block_present:
                raise ValueError(
                    f"mesh mixes the legacy {DATA_AXIS!r} axis with block "
                    f"axes {block_present}; use one naming scheme")
            names_map[0] = DATA_AXIS
        elif block_present:
            legacy = False
            for dim, nm in enumerate(BLOCK_AXES):
                if nm not in mesh.axis_names:
                    continue
                if dim >= ndim:
                    if int(mesh.shape[nm]) > 1:
                        raise ValueError(
                            f"{nm!r} has {int(mesh.shape[nm])} devices but "
                            f"the field is {ndim}D; 2D fields shard over "
                            "('data_y','data_z') only")
                    continue
                names_map[dim] = nm
        else:
            raise ValueError(
                f"mesh axes {mesh.axis_names} include no data axis "
                f"(one of {ALL_DATA_AXES}); build one with "
                "launch.mesh.make_data_mesh / make_block_mesh")
    names = tuple(names_map.get(d) for d in range(ndim))
    sharded = []
    for dim in range(ndim):
        nm = names[dim]
        if nm is None:
            continue
        n = int(mesh.shape[nm])
        if n >= 2:
            sharded.append(BlockAxis(dim, nm, n, -(-shape[dim] // n)))
    return BlockPlan(shape, names, tuple(sharded), legacy)


def _pad_blocks(x: jnp.ndarray, plan: BlockPlan) -> jnp.ndarray:
    """Zero-pad every sharded axis to ``n * L`` (kernels mask the true
    domain boundary in global coordinates, so pad content is never read
    by a real vertex; pad outputs are dropped on unpad)."""
    pads = [(0, 0)] * x.ndim
    changed = False
    for a in plan.sharded:
        want = a.n * a.L
        if x.shape[a.dim] != want:
            pads[a.dim] = (0, want - x.shape[a.dim])
            changed = True
    return jnp.pad(x, pads) if changed else x


def _unpad(x: jnp.ndarray, plan: BlockPlan) -> jnp.ndarray:
    """Crop a padded global array back to the real field shape."""
    return x[tuple(slice(0, s) for s in plan.shape)]


# ---------------------------------------------------------------------------
# halo exchange
# ---------------------------------------------------------------------------

def _chain_perms(n: int):
    fwd = [(d, d + 1) for d in range(n - 1)]
    bwd = [(d + 1, d) for d in range(n - 1)]
    return fwd, bwd


def halo_exchange(x: jnp.ndarray, axis_name: str, n_dev: int, *,
                  axis: int = 0,
                  depth: int = 1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``depth``-layer ghost faces from the chain neighbors.

    Returns ``(lo, hi)``: ``lo`` is the previous device's last ``depth``
    layers along ``axis``, ``hi`` the next device's first. The chain does
    NOT wrap: device 0's ``lo`` and device n-1's ``hi`` are zeros, which
    is safe because the kernels mask true domain boundaries themselves,
    in global coordinates, and the fix pass never pulls across them.
    1-device axes emit NO collective at all — the zero faces are built
    locally instead of round-tripping a degenerate self-permute.
    """
    size = x.shape[axis]
    last = jax.lax.slice_in_dim(x, size - depth, size, axis=axis)
    first = jax.lax.slice_in_dim(x, 0, depth, axis=axis)
    if n_dev <= 1:
        return jnp.zeros_like(last), jnp.zeros_like(first)
    fwd, bwd = _chain_perms(n_dev)
    lo = jax.lax.ppermute(last, axis_name, fwd)
    hi = jax.lax.ppermute(first, axis_name, bwd)
    return lo, hi


def with_halo(x: jnp.ndarray, axis_name: str, n_dev: int) -> jnp.ndarray:
    """Extend a local (L, ...) slab block to (L+2, ...) with exchanged
    ghost slabs on both ends (the legacy 1-axis helper; block meshes use
    ``block_halo``)."""
    lo, hi = halo_exchange(x, axis_name, n_dev)
    return jnp.concatenate([lo, x, hi], axis=0)


def block_halo(x: jnp.ndarray, plan: BlockPlan, depth: int, *,
               axis_offset: int = 0) -> jnp.ndarray:
    """Two-phase axis-ordered face exchange: extend ``x`` with ``depth``
    ghost layers along every sharded axis of ``plan``, in field-axis
    order. Later axes take their faces from the *already-extended* array,
    so a face sent in phase b carries the phase-a ghosts at its rim —
    after all phases every edge/corner ghost of the 26-stencil holds the
    correct diagonal-neighbor value without any dedicated diagonal sends
    (DESIGN.md §9). ``axis_offset`` shifts field axes for stacked
    payloads (e.g. a leading channel axis)."""
    ext = x
    for a in plan.sharded:
        ax = a.dim + axis_offset
        lo, hi = halo_exchange(ext, a.name, a.n, axis=ax, depth=depth)
        ext = jnp.concatenate([lo, ext, hi], axis=ax)
    return ext


def exchange_tree(tree, plan: BlockPlan, depth: int):
    """Halo-extend every leaf of a field-shaped pytree, reusing ONE
    stacked exchange per dtype group instead of one per leaf: leaves of
    equal dtype stack along a new leading axis, ride a single two-phase
    face exchange, and unstack. For the fix loop's constant topology
    (4 int32 label/code leaves + 2 bool extremum masks + 1 float lower
    bound) this cuts the per-axis topology exchange from 7 ppermute
    pairs to 3."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    by_dtype: Dict[str, List[int]] = {}
    for i, leaf in enumerate(leaves):
        # mszlint: disable=transfer-discipline -- runs under the shard_map
        # trace; asarray of a tracer is a no-op cast
        by_dtype.setdefault(str(jnp.asarray(leaf).dtype), []).append(i)
    out: List[Optional[jnp.ndarray]] = [None] * len(leaves)
    for idxs in by_dtype.values():
        # mszlint: disable=transfer-discipline -- same trace-context no-op
        stacked = jnp.stack([jnp.asarray(leaves[i]) for i in idxs])
        ext = block_halo(stacked, plan, depth, axis_offset=1)
        for k, i in enumerate(idxs):
            out[i] = ext[k]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# traced block coordinates
# ---------------------------------------------------------------------------

def _origins(plan: BlockPlan) -> List[jnp.ndarray]:
    """Per field axis, this device's global block origin (traced)."""
    o = [jnp.int32(0)] * plan.ndim
    for a in plan.sharded:
        o[a.dim] = jax.lax.axis_index(a.name).astype(jnp.int32) * a.L
    return o


def _coords(plan: BlockPlan, origins, start: Sequence[int]):
    """Kernel global-placement kwargs for a call whose array begins at
    block layer ``start[d]`` along field axis d (negative = inside the
    ghost ring). 2D fields use the slab/col pairs (the kernel plane's
    row axis is unused)."""
    s = plan.shape
    o = [origins[d] + jnp.int32(start[d]) for d in range(plan.ndim)]
    if plan.ndim == 3:
        return dict(slab_lo=o[0], n_slabs_total=s[0],
                    row_lo=o[1], n_rows_total=s[1],
                    col_lo=o[2], n_cols_total=s[2])
    return dict(slab_lo=o[0], n_slabs_total=s[0],
                row_lo=0, n_rows_total=1,
                col_lo=o[1], n_cols_total=s[1])


def _sl(plan: BlockPlan, per_axis: Dict[int, slice],
        offset: int = 0) -> Tuple[slice, ...]:
    """A slice tuple: ``per_axis[dim]`` on listed SHARDED dims, full
    slices elsewhere; ``offset`` prepends full slices (stacked arrays)."""
    out = [slice(None)] * (plan.ndim + offset)
    for dim, s in per_axis.items():
        out[dim + offset] = s
    return tuple(out)


def _real_weight(plan: BlockPlan, origins, dtype=jnp.int32) -> jnp.ndarray:
    """Broadcastable 0/1 weight marking the real (non-pad) vertices of
    this device's block."""
    w = jnp.ones((1,) * plan.ndim, dtype)
    for a in plan.sharded:
        line = ((origins[a.dim] + jnp.arange(a.L, dtype=jnp.int32))
                < plan.shape[a.dim]).astype(dtype)
        shp = [1] * plan.ndim
        shp[a.dim] = a.L
        w = w * line.reshape(shp)
    return w


def _resolve_modes(plan: BlockPlan, overlap: Optional[bool],
                   worklist: Optional[bool]) -> Tuple[bool, bool]:
    """(use_overlap, use_worklist) for a plan.

    Overlap needs >= 3 vertices per sharded axis (the boundary shells'
    2-deep faces and the interior pass must not degenerate); default ON
    for block meshes, OFF for legacy 1-axis plans (whose schedule stays
    byte-stable with PR 4). The worklist needs >= 2 (the 2-vertex dirt
    radius must stay within one ppermute hop); default ON, as in PR 6.
    """
    # mszlint: disable=transfer-discipline -- plan/overlap are host config
    sharded = bool(plan.sharded)
    can_overlap = sharded and plan.min_block() >= 3
    use_overlap = (can_overlap if overlap is None
                   # mszlint: disable=transfer-discipline -- host config
                   else bool(overlap) and can_overlap)
    if overlap is None and plan.legacy:
        use_overlap = False
    can_wl = sharded and plan.min_block() >= 2
    use_wl = (worklist if worklist is not None else True) and can_wl
    return use_overlap, use_wl


# ---------------------------------------------------------------------------
# the SPMD fix iteration (overlap OFF: exchange -> extrema -> mask
# exchange -> fix, the PR-4 schedule generalized to N sharded axes)
# ---------------------------------------------------------------------------

def _stack_masks(selfe, dem, pro, up_c, inner):
    return jnp.stack([selfe[inner], dem[inner], pro[inner], up_c[inner]])


def _step_plain(g_loc, topo_ext, run, src, cache, *, plan: BlockPlan,
                interpret: bool, worklist: bool):
    """One non-overlapped fix iteration on a local block.

    ``topo_ext``: topology pytree with (constant) 1-deep halos. With
    ``worklist`` the kernels sit under ``lax.cond(run, ...)`` while every
    collective stays unconditional; a skipped device re-sends its
    ``cache`` — the 1-deep mask faces of the last iteration it ran,
    still exact because nothing within its dependency radius changed —
    and the two-phase exchange relays fresh corner data through it
    untouched. Returns (g2, viol, src2, cache2, run2).
    """
    origins = _origins(plan)
    names = plan.axis_names()
    inner = _sl(plan, {a.dim: slice(1, -1) for a in plan.sharded})
    start = [0] * plan.ndim
    for a in plan.sharded:
        start[a.dim] = -1
    coords = _coords(plan, origins, start)
    block = plan.block_shape()

    g_ext = block_halo(g_loc, plan, 1)

    def do_masks(_):
        up_c, _, selfe, dem, pro = extrema_masks_pallas(
            g_ext, topo_ext.M, topo_ext.m,
            topo_ext.is_max.astype(jnp.int32),
            topo_ext.is_min.astype(jnp.int32),
            interpret=interpret, **coords)
        return _stack_masks(selfe, dem, pro, up_c, inner)

    if worklist:
        stacked = jax.lax.cond(
            run, do_masks, lambda _: jnp.zeros((4,) + block, jnp.int32),
            None)
        send = stacked
        cache2 = []
        for i, a in enumerate(plan.sharded):
            ax = 1 + a.dim
            f_lo = jax.lax.slice_in_dim(stacked, 0, 1, axis=ax)
            f_hi = jax.lax.slice_in_dim(stacked, a.L - 1, a.L, axis=ax)
            use_lo = jnp.where(run, f_lo, cache[i][0])
            use_hi = jnp.where(run, f_hi, cache[i][1])
            mid = jax.lax.slice_in_dim(send, 1, a.L - 1, axis=ax)
            send = jnp.concatenate([use_lo, mid, use_hi], axis=ax)
            cache2.append((use_lo, use_hi))
        cache2 = tuple(cache2)
    else:
        stacked = do_masks(None)
        send, cache2 = stacked, cache

    m_ext = block_halo(send, plan, 1, axis_offset=1)
    self_e, dem_e, pro_e, upc_e = m_ext

    def do_fix(_):
        g2_ext, _, _ = fix_pass_pallas(
            g_ext, topo_ext.lower, self_e, dem_e, pro_e, upc_e,
            topo_ext.dn_c, interpret=interpret, **coords)
        return g2_ext[inner]

    if worklist:
        g2_loc = jax.lax.cond(run, do_fix, lambda _: g_loc, None)
    else:
        g2_loc = do_fix(None)

    real = _real_weight(plan, origins)
    src_fresh = jnp.sum((stacked[0] + stacked[1] + stacked[2])
                        * real).astype(jnp.int32)
    src2 = jnp.where(run, src_fresh, src) if worklist else src_fresh
    viol = jax.lax.psum(src2, names) if names else src2

    run2 = _dirt_flags(g2_loc, g_loc, real, plan) if worklist else run
    return g2_loc, viol, src2, cache2, run2


def _dirt_flags(g2_loc, g_loc, real, plan: BlockPlan):
    """Next iteration's run flag: did g change inside this block, or
    within 2 layers of a face whose neighbor (or diagonal neighbor, via
    the axis-ordered fold) must hear about it? A vertex's next masks
    depend on g within 1 and its next fix output on g within 2, so a
    device may skip iff no g change landed within 2 vertices of its
    block — the PR-6 invariant per block."""
    changed = (g2_loc != g_loc) & (real != 0)
    own_any = jnp.any(changed)
    recv_any = jnp.bool_(False)
    for a in plan.sharded:
        lo_edge = jnp.any(jax.lax.slice_in_dim(changed, 0, 2, axis=a.dim))
        hi_edge = jnp.any(jax.lax.slice_in_dim(changed, a.L - 2, a.L,
                                               axis=a.dim))
        fwd, bwd = _chain_perms(a.n)
        dirt_lo = jax.lax.ppermute(hi_edge | recv_any, a.name, fwd)
        dirt_hi = jax.lax.ppermute(lo_edge | recv_any, a.name, bwd)
        recv_any = recv_any | dirt_lo | dirt_hi
    return own_any | recv_any


# ---------------------------------------------------------------------------
# the SPMD fix iteration (overlap ON: one 2-deep g exchange per
# iteration; interior pass has no halo dependency and overlaps it)
# ---------------------------------------------------------------------------

def _overlap_masks(g_loc, ext2, topo_ext2, *, plan, origins, interpret):
    """Fresh masks on the 1-deep-extended block (ext1 layout), computed
    from 2-deep ``g`` ghosts only: the interior pass runs on the bare
    block (no halo dependency), the per-axis boundary shells recompute
    the face ring AND the ghost ring locally — exactly what the
    overlap-OFF schedule's mask exchange would have delivered, because
    both kernels place every vertex in global coordinates."""
    ndim = plan.ndim
    c2 = _sl(plan, {a.dim: slice(2, -2) for a in plan.sharded})

    def extrema(g_arr, topo_idx, start):
        t = jax.tree_util.tree_map(lambda x: x[topo_idx], topo_ext2)
        up_c, _, selfe, dem, pro = extrema_masks_pallas(
            g_arr, t.M, t.m, t.is_max.astype(jnp.int32),
            t.is_min.astype(jnp.int32), interpret=interpret,
            **_coords(plan, origins, start))
        return jnp.stack([selfe, dem, pro, up_c])

    # interior: exact at >= 1 vertex from every sharded face
    m_int = extrema(g_loc, c2, [0] * ndim)

    ext1_shape = tuple(s + (2 if any(a.dim == d for a in plan.sharded)
                            else 0)
                       for d, s in enumerate(plan.block_shape()))
    m1 = jnp.zeros((4,) + ext1_shape, jnp.int32)
    m1 = m1.at[_sl(plan, {a.dim: slice(1, -1) for a in plan.sharded},
                   offset=1)].set(m_int)

    for a in plan.sharded:
        others = {b.dim: slice(0, b.L + 4) for b in plan.sharded
                  if b.dim != a.dim}
        keep_o = {b.dim: slice(1, b.L + 3) for b in plan.sharded
                  if b.dim != a.dim}
        start = [0] * ndim
        for b in plan.sharded:
            start[b.dim] = -2
        # low shell: g layers -2..1 -> masks exact at layers -1..0
        idx = _sl(plan, {**others, a.dim: slice(0, 4)})
        m_lo = extrema(ext2[idx], idx, start)
        keep = _sl(plan, {**keep_o, a.dim: slice(1, 3)}, offset=1)
        m1 = m1.at[_sl(plan, {a.dim: slice(0, 2)}, offset=1)].set(m_lo[keep])
        # high shell: g layers L-2..L+1 -> masks exact at L-1..L
        start_hi = list(start)
        start_hi[a.dim] = a.L - 2
        idx = _sl(plan, {**others, a.dim: slice(a.L, a.L + 4)})
        m_hi = extrema(ext2[idx], idx, start_hi)
        m1 = m1.at[_sl(plan, {a.dim: slice(a.L, a.L + 2)},
                       offset=1)].set(m_hi[keep])
    return m1


def _overlap_fix(g_loc, ext2, m1, topo_ext2, *, plan, origins, interpret):
    """The fix pass split into interior + per-axis boundary shells; the
    interior call touches no ghost data and overlaps the exchange that
    fed ``m1``'s shells."""
    ndim = plan.ndim

    def fix(g_arr, masks, low, dnc, start):
        g2, _, _ = fix_pass_pallas(
            g_arr, low, masks[0], masks[1], masks[2], masks[3], dnc,
            interpret=interpret, **_coords(plan, origins, start))
        return g2

    sh_dims = {a.dim: a for a in plan.sharded}
    g2 = jnp.zeros_like(g_loc)

    # interior: block layers [1, L-1), exact (kept) at [2, L-2)
    ci = _sl(plan, {d: slice(1, -1) for d in sh_dims})
    ci_m1 = _sl(plan, {d: slice(2, -2) for d in sh_dims}, offset=1)
    ci_t = _sl(plan, {d: slice(3, a.L + 1) for d, a in sh_dims.items()})
    start = [0] * ndim
    for d in sh_dims:
        start[d] = 1
    g2_int = fix(g_loc[ci], m1[ci_m1], topo_ext2.lower[ci_t],
                 topo_ext2.dn_c[ci_t], start)
    keep_i = _sl(plan, {d: slice(1, -1) for d in sh_dims})
    g2 = g2.at[_sl(plan, {d: slice(2, -2) for d in sh_dims})].set(
        g2_int[keep_i])

    for a in plan.sharded:
        o_m1 = {b.dim: slice(0, b.L + 2) for b in plan.sharded
                if b.dim != a.dim}
        o_g = {b.dim: slice(1, b.L + 3) for b in plan.sharded
               if b.dim != a.dim}
        keep_o = {b.dim: slice(1, b.L + 1) for b in plan.sharded
                  if b.dim != a.dim}
        start = [0] * ndim
        for b in plan.sharded:
            if b.dim != a.dim:
                start[b.dim] = -1
        # low shell: targets at block layers 0..1
        start_lo = list(start)
        start_lo[a.dim] = -1
        m_idx = _sl(plan, {**o_m1, a.dim: slice(0, 4)}, offset=1)
        g_idx = _sl(plan, {**o_g, a.dim: slice(1, 5)})
        out = fix(ext2[g_idx], m1[m_idx], topo_ext2.lower[g_idx],
                  topo_ext2.dn_c[g_idx], start_lo)
        keep = _sl(plan, {**keep_o, a.dim: slice(1, 3)})
        g2 = g2.at[_sl(plan, {a.dim: slice(0, 2)})].set(out[keep])
        # high shell: targets at block layers L-2..L-1
        start_hi = list(start)
        start_hi[a.dim] = a.L - 3
        m_idx = _sl(plan, {**o_m1, a.dim: slice(a.L - 2, a.L + 2)},
                    offset=1)
        g_idx = _sl(plan, {**o_g, a.dim: slice(a.L - 1, a.L + 3)})
        out = fix(ext2[g_idx], m1[m_idx], topo_ext2.lower[g_idx],
                  topo_ext2.dn_c[g_idx], start_hi)
        g2 = g2.at[_sl(plan, {a.dim: slice(a.L - 2, a.L)})].set(out[keep])
    return g2


def _step_overlap(g_loc, topo_ext2, run, src, *, plan: BlockPlan,
                  interpret: bool, worklist: bool, part: str = "full"):
    """One overlapped fix iteration: a single 2-deep two-phase ``g``
    face exchange (which subsumes the mask exchange — boundary masks are
    recomputed locally from the deep ghosts) plus interior kernels that
    depend only on local data, so XLA schedules them while the ppermutes
    are in flight. ``part`` carves out the probe surfaces the stream
    stats use ("interior" skips the exchange and shells, "exchange" only
    moves ghosts). Returns (g2, viol, src2, run2)."""
    origins = _origins(plan)
    names = plan.axis_names()
    real = _real_weight(plan, origins)
    c2 = _sl(plan, {a.dim: slice(2, -2) for a in plan.sharded})

    if part == "exchange":
        # the probe's viol output consumes every exchanged element so
        # XLA cannot dead-code the ppermutes away
        ext2 = block_halo(g_loc, plan, 2)
        return g_loc, jnp.sum(ext2).astype(jnp.int32), src, run

    if part == "interior":
        pads = [(2, 2) if any(a.dim == d for a in plan.sharded) else (0, 0)
                for d in range(plan.ndim)]
        ext2 = jnp.pad(g_loc, pads)
    else:
        ext2 = block_halo(g_loc, plan, 2)

    def do_masks(_):
        return _overlap_masks(g_loc, ext2, topo_ext2, plan=plan,
                              origins=origins, interpret=interpret)

    ext1_shape = tuple(s + (2 if any(a.dim == d for a in plan.sharded)
                            else 0)
                       for d, s in enumerate(plan.block_shape()))
    if worklist:
        m1 = jax.lax.cond(run, do_masks,
                          lambda _: jnp.zeros((4,) + ext1_shape, jnp.int32),
                          None)
    else:
        m1 = do_masks(None)

    def do_fix(_):
        return _overlap_fix(g_loc, ext2, m1, topo_ext2, plan=plan,
                            origins=origins, interpret=interpret)

    if worklist:
        g2_loc = jax.lax.cond(run, do_fix, lambda _: g_loc, None)
    else:
        g2_loc = do_fix(None)

    m1c = m1[_sl(plan, {a.dim: slice(1, -1) for a in plan.sharded},
                 offset=1)]
    src_fresh = jnp.sum((m1c[0] + m1c[1] + m1c[2]) * real).astype(jnp.int32)
    src2 = jnp.where(run, src_fresh, src) if worklist else src_fresh
    if part == "interior":
        return g2_loc, src2, src2, run
    viol = jax.lax.psum(src2, names) if names else src2
    run2 = _dirt_flags(g2_loc, g_loc, real, plan) if worklist else run
    return g2_loc, viol, src2, run2


def _moved_count(g2_loc, g_loc, plan: BlockPlan) -> jnp.ndarray:
    """How many real (unpadded) values one pass changed, summed over
    every sharded axis: the loop's progress count (DESIGN.md §2)."""
    real = _real_weight(plan, _origins(plan))
    n = jnp.sum((g2_loc != g_loc) * real).astype(jnp.int32)
    names = plan.axis_names()
    return jax.lax.psum(n, names) if names else n


# ---------------------------------------------------------------------------
# full distributed loop (one shard_map around the whole while_loop)
# ---------------------------------------------------------------------------

def sharded_fix(g0: jnp.ndarray, topo, mesh: Mesh, *,
                max_iters: Optional[int] = None,
                axis_name: Optional[str] = None,
                interpret: Optional[bool] = None,
                worklist: Optional[bool] = None,
                overlap: Optional[bool] = None):
    """Run the fused fix loop to convergence, distributed over ``mesh``'s
    data axes (1D slab chains or 2D/3D block meshes). Returns (g, iters,
    converged), bitwise equal to ``fused_fix(..., backend="pallas")``.

    The entire while_loop executes inside ONE shard_map: the (constant)
    topology halos are exchanged once (one stacked exchange per dtype
    group), only ``g`` — and, without overlap, mask — faces move per
    iteration, and the convergence predicate is the violation count
    psummed over every sharded axis, replicated so all devices decide
    identically. So is the count of values the pass moved: the loop
    stops where ``core.backend.keep_going`` says, as the single-device
    loops do.

    ``worklist`` (default on with >= 2 vertices per sharded block axis)
    is the per-block dirty skip of PR 6; ``overlap`` (default on for
    block meshes with >= 3-vertex blocks, off for legacy ``data`` chains)
    selects the interior/boundary split schedule with its single 2-deep
    ``g`` exchange. All four combinations produce bitwise-identical
    trajectories — only the collective schedule changes.
    """
    if interpret is None:
        interpret = default_interpret()
    plan = plan_blocks(g0.shape, mesh, axis_name)
    use_overlap, use_wl = _resolve_modes(plan, overlap, worklist)
    g_p = _pad_blocks(g0, plan)
    topo_p = jax.tree_util.tree_map(lambda x: _pad_blocks(x, plan), topo)
    depth = 2 if use_overlap else 1
    block = plan.block_shape()
    moved = functools.partial(_moved_count, plan=plan)

    def spmd(g_loc, topo_loc):
        topo_ext = exchange_tree(topo_loc, plan, depth)
        if use_overlap:
            step = functools.partial(_step_overlap, plan=plan,
                                     interpret=interpret, worklist=use_wl)

            def body(state):
                g, it, _, _, src, run = state
                g2, viol2, src2, run2 = step(g, topo_ext, run, src)
                return g2, it + 1, viol2, moved(g2, g), src2, run2
        else:
            step_p = functools.partial(_step_plain, plan=plan,
                                       interpret=interpret, worklist=use_wl)

            def body(state):
                g, it, _, _, src, run, cache = state
                g2, viol2, src2, cache2, run2 = step_p(
                    g, topo_ext, run, src, cache)
                return g2, it + 1, viol2, moved(g2, g), src2, run2, cache2

        def cond(state):
            return keep_going(state[2], state[3], state[1], max_iters)

        run0 = jnp.bool_(True)
        src0 = jnp.int32(0)
        if use_overlap:
            g1, viol1, src1, run1 = step(g_loc, topo_ext, run0, src0)
            out = jax.lax.while_loop(
                cond, body, (g1, jnp.int32(1), viol1, moved(g1, g_loc),
                             src1, run1))
        else:
            cache0 = tuple(
                (jnp.zeros((4,) + tuple(1 if d == a.dim else s
                                        for d, s in enumerate(block)),
                           jnp.int32),) * 2
                for a in plan.sharded) if use_wl else tuple(
                    ((jnp.int32(0),) * 2) for a in plan.sharded)
            cache0 = tuple(cache0)
            g1, viol1, src1, cache1, run1 = step_p(
                g_loc, topo_ext, run0, src0, cache0)
            out = jax.lax.while_loop(
                cond, body, (g1, jnp.int32(1), viol1, moved(g1, g_loc),
                             src1, run1, cache1))
        return out[0], out[1], out[2]

    spec = plan.spec()
    g, iters, viol = jax.shard_map(
        spmd, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec, PartitionSpec(), PartitionSpec()),
        check_vma=False)(g_p, topo_p)
    return _unpad(g, plan), iters, viol == 0


# ---------------------------------------------------------------------------
# halo accounting + overlap probe (stream/service observability, §9)
# ---------------------------------------------------------------------------

def halo_plan(shape: Sequence[int], dtype, mesh: Mesh, *,
              axis_name: Optional[str] = None,
              overlap: Optional[bool] = None,
              worklist: Optional[bool] = None) -> Dict[str, int]:
    """Analytic per-mesh-axis halo traffic of ONE fix iteration, in
    bytes summed over all devices (both directions, including the
    corner/edge rows that later phases relay). Overlap-OFF counts the g
    faces plus the stacked 4-channel int32 mask faces; overlap-ON counts
    the single 2-deep g exchange. The stream scheduler multiplies by the
    observed iteration counts to surface live per-axis exchange bytes in
    ``CompressionService.stats()``."""
    plan = plan_blocks(shape, mesh, axis_name)
    use_overlap, _ = _resolve_modes(plan, overlap, worklist)
    item = jnp.dtype(dtype).itemsize
    out: Dict[str, int] = {}

    def sweep(depth, channels, itemsize):
        dims = list(plan.block_shape())
        for a in plan.sharded:
            face = depth * channels * itemsize
            for d, s in enumerate(dims):
                if d != a.dim:
                    face *= s
            senders = 2 * (a.n - 1)
            for b in plan.sharded:
                if b.dim != a.dim:
                    senders *= b.n
            out[a.name] = out.get(a.name, 0) + face * senders
            dims[a.dim] += 2 * depth
    if use_overlap:
        sweep(2, 1, item)
    else:
        sweep(1, 1, item)
        sweep(1, 4, jnp.dtype(jnp.int32).itemsize)
    return out


def time_step_parts(g0: jnp.ndarray, topo, mesh: Mesh, *,
                    axis_name: Optional[str] = None,
                    interpret: Optional[bool] = None,
                    reps: int = 3) -> Dict[str, float]:
    """Measure one overlapped iteration's interior pass, ghost exchange,
    and full step (seconds, best of ``reps``) on real arrays — the
    interior/boundary timing surface the service stats expose so the
    overlap win is observable in serving. Falls back to timing the plain
    schedule as "full" when the plan cannot overlap."""
    import time as _time
    if interpret is None:
        interpret = default_interpret()
    plan = plan_blocks(g0.shape, mesh, axis_name)
    use_overlap, _ = _resolve_modes(plan, True, False)
    g_p = _pad_blocks(g0, plan)
    topo_p = jax.tree_util.tree_map(lambda x: _pad_blocks(x, plan), topo)
    spec = plan.spec()

    def make(part):
        def spmd(g_loc, topo_loc):
            topo_ext = exchange_tree(topo_loc, plan, 2)
            g2, viol, _, _ = _step_overlap(
                g_loc, topo_ext, jnp.bool_(True), jnp.int32(0), plan=plan,
                interpret=interpret, worklist=False, part=part)
            return g2, viol

        def plain(g_loc, topo_loc):
            topo_ext = exchange_tree(topo_loc, plan, 1)
            g2, viol, _, _, _ = _step_plain(
                g_loc, topo_ext, jnp.bool_(True), jnp.int32(0), (),
                plan=plan, interpret=interpret, worklist=False)
            return g2, viol

        fn = spmd if use_overlap else plain
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(spec, spec),
            out_specs=(spec, PartitionSpec()), check_vma=False))

    parts = ("interior", "exchange", "full") if use_overlap else ("full",)
    res: Dict[str, float] = {}
    for part in parts:
        fn = make(part)
        g2, v = fn(g_p, topo_p)       # compile + warm
        jax.block_until_ready((g2, v))
        best = float("inf")
        for _ in range(max(1, reps)):
            t0 = _time.perf_counter()
            g2, v = fn(g_p, topo_p)
            jax.block_until_ready((g2, v))
            best = min(best, _time.perf_counter() - t0)
        res[f"t_{part}_s"] = best
    if use_overlap:
        res["t_boundary_s"] = max(0.0, res["t_full_s"] - res["t_interior_s"])
    # mszlint: disable=transfer-discipline -- host mode flag
    res["overlap"] = bool(use_overlap)
    return res


# ---------------------------------------------------------------------------
# sharded base transform (device-resident compression path, DESIGN.md §4)
# ---------------------------------------------------------------------------

def sharded_transform(f: jnp.ndarray, step, mesh: Mesh, *,
                      axis_name: Optional[str] = None,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """Quantize + integer Lorenzo over the mesh: each device transforms
    its own block after one backward 1-deep face exchange per sharded
    axis (the Lorenzo stencil is backward-only; the two-phase ordering
    delivers the backward edge/corner ghosts transitively). The kernel
    runs in global slab coordinates, zero ghosts at true domain starts
    match the codec's zero-padding, and in-plane ghost columns feed the
    in-plane backward differences — residual codes are bitwise equal to
    a single-device run."""
    if interpret is None:
        interpret = default_interpret()
    plan = plan_blocks(f.shape, mesh, axis_name)
    f_p = _pad_blocks(f, plan)
    step_arr = typed_operand(step, f.dtype)

    def spmd(f_loc):
        ext = f_loc
        for a in plan.sharded:
            size = ext.shape[a.dim]
            last = jax.lax.slice_in_dim(ext, size - 1, size, axis=a.dim)
            if a.n > 1:
                fwd, _ = _chain_perms(a.n)
                lo = jax.lax.ppermute(last, a.name, fwd)
            else:
                lo = jnp.zeros_like(last)
            ext = jnp.concatenate([lo, ext], axis=a.dim)
        sh0 = next((a for a in plan.sharded if a.dim == 0), None)
        slab_lo = (jax.lax.axis_index(sh0.name).astype(jnp.int32) * sh0.L - 1
                   if sh0 is not None else 0)
        r_ext = lorenzo_quant_pallas(ext, step_arr, interpret=interpret,
                                     slab_lo=slab_lo)
        drop = tuple(slice(1, None) if any(a.dim == d
                                           for a in plan.sharded)
                     else slice(None) for d in range(plan.ndim))
        return r_ext[drop]

    spec = plan.spec()
    r = jax.shard_map(spmd, mesh=mesh, in_specs=(spec,), out_specs=spec,
                      check_vma=False)(f_p)
    return _unpad(r, plan)


def sharded_scatter_edits(f_hat: jnp.ndarray, idx, val, mesh: Mesh, *,
                          axis_name: Optional[str] = None) -> jnp.ndarray:
    """Edit scatter over the mesh (the device decompression path's
    g = f_hat + delta, DESIGN.md §5): ``f_hat`` stays block-sharded, the
    (small) edit stream is replicated, and each device decomposes every
    global flat index into field coordinates, keeps exactly those inside
    its own block, and scatter-adds at the local offset — no collectives.
    Out-of-block indices (including the batched path's one-past-the-end
    padding) are remapped out of range and dropped, never wrapped —
    bitwise equal to the single-device scatter."""
    plan = plan_blocks(f_hat.shape, mesh, axis_name)
    f_p = _pad_blocks(f_hat, plan)
    block = plan.block_shape()
    shape = plan.shape
    loc_size = 1
    for s in block:
        # mszlint: disable=transfer-discipline -- block shape is host ints
        loc_size *= int(s)

    def spmd(fh_loc, idx_g, val_g):
        origins = _origins(plan)
        flat = idx_g.astype(jnp.int32)
        coords = []
        rem = flat
        for d in range(plan.ndim - 1, -1, -1):
            coords.append(rem % jnp.int32(shape[d]))
            rem = rem // jnp.int32(shape[d])
        coords = coords[::-1]
        oob = (flat < 0) | (flat >= jnp.int32(
            functools.reduce(lambda a, b: a * b, shape)))
        loc = jnp.int32(0)
        for d in range(plan.ndim):
            c = coords[d] - origins[d]
            oob = oob | (c < 0) | (c >= jnp.int32(block[d]))
            loc = loc * jnp.int32(block[d]) + c
        loc = jnp.where(oob, jnp.int32(loc_size), loc)
        out = fh_loc.reshape(-1)
        out = out.at[loc].add(val_g.astype(out.dtype), mode="drop")
        return out.reshape(fh_loc.shape)

    spec = plan.spec()
    idx_dev = typed_operand(idx, jnp.int32)
    val_dev = (val if isinstance(val, jnp.ndarray)
               else typed_operand(val, np.asarray(val).dtype))
    out = jax.shard_map(spmd, mesh=mesh,
                        in_specs=(spec, PartitionSpec(), PartitionSpec()),
                        out_specs=spec, check_vma=False)(
        f_p, idx_dev, val_dev)
    return _unpad(out, plan)


def sharded_reconstruct(r: jnp.ndarray, step, dtype, mesh: Mesh, *,
                        axis_name: Optional[str] = None) -> jnp.ndarray:
    """Inverse transform over the mesh: along every sharded axis the
    global cumsum becomes local-cumsum + an exclusive prefix of
    per-device block totals (one all_gather of a face per axis);
    unsharded axes cumsum locally. Int32 arithmetic is exact and
    wraparound-commutative, and the final dequantization multiply is
    elementwise — bitwise equal to single-device ``sz_inverse``."""
    plan = plan_blocks(r.shape, mesh, axis_name)
    r_p = _pad_blocks(r, plan)
    step_arr = typed_operand(step, dtype)
    by_dim = {a.dim: a for a in plan.sharded}

    def spmd(r_loc):
        from ..compress.szlike import int32_cumsum
        q = r_loc
        for d in range(plan.ndim):
            # mszlint: disable=int32-range -- mirrors sz_inverse, whose
            # callers gate on codes_fit_int32 before any decode
            q = int32_cumsum(q, d)
            a = by_dim.get(d)
            if a is None:
                continue
            size = q.shape[d]
            last = jax.lax.slice_in_dim(q, size - 1, size, axis=d)
            totals = jax.lax.all_gather(last, a.name)       # (n, ...)
            before = (jnp.arange(a.n) < jax.lax.axis_index(a.name))
            before = before.astype(jnp.int32).reshape(
                (-1,) + (1,) * q.ndim)
            q = q + jnp.sum(totals * before, axis=0, dtype=jnp.int32)
        return q.astype(dtype) * step_arr

    spec = plan.spec()
    out = jax.shard_map(spmd, mesh=mesh, in_specs=(spec,), out_specs=spec,
                        check_vma=False)(r_p)
    return _unpad(out, plan)


# ---------------------------------------------------------------------------
# the registered backend
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedBackend:
    """Block-sharded SPMD execution over a mesh's data axes.

    ``mesh=None`` (the registry instance) resolves the active mesh at
    call time; ``resolve_backend``/``fused_fix`` bind it into a concrete
    instance before jit so compilation caches key on the actual mesh.
    ``axis_name=None`` auto-detects the decomposition from the mesh's
    axis names (legacy ``data`` chains and ``data_*`` block meshes);
    an explicit name forces the 1-axis legacy layout.

    ``worklist``: per-block dirty skipping inside ``fix_loop`` (None =
    on whenever blocks keep >= 2 vertices per sharded axis; see
    ``sharded_fix``). ``overlap``: the interior/boundary split schedule
    (None = on for block meshes with >= 3-vertex blocks). Neither ever
    changes results — only which kernels run and when ghosts move.
    """
    name: str = "sharded"
    mesh: Optional[Mesh] = None
    axis_name: Optional[str] = None
    interpret: Optional[bool] = None
    worklist: Optional[bool] = None
    overlap: Optional[bool] = None

    def with_mesh(self, mesh: Mesh) -> "ShardedBackend":
        """A copy of this backend bound to ``mesh``."""
        return dataclasses.replace(self, mesh=mesh)

    def bind(self) -> "ShardedBackend":
        """Freeze the mesh this instance will run on (explicit mesh wins,
        else the active ``with mesh:`` context)."""
        if self.mesh is not None:
            return self
        m = active_data_mesh(self.axis_name)
        if m is None:
            raise ValueError(
                "sharded backend needs a mesh: pass mesh=..., or enter a "
                "`with mesh:` context whose mesh has a data axis (one of "
                f"{ALL_DATA_AXES})")
        return self.with_mesh(m)

    def _interpret(self) -> bool:
        return default_interpret() if self.interpret is None else self.interpret

    def n_data_devices(self) -> int:
        """Devices across this instance's data axes (0 when no mesh is
        bound or active)."""
        mesh = self.mesh if self.mesh is not None \
            else active_data_mesh(self.axis_name)
        return data_axis_size(mesh, self.axis_name)

    def supports(self, shape: Tuple[int, ...], dtype) -> bool:
        """Non-empty 2D/3D floating fields, given >= 1 data device."""
        return (len(shape) in (2, 3) and min(shape) >= 1
                and jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
                and self.n_data_devices() >= 1)

    # -- protocol: one fused iteration on global arrays ----------------
    def fused_step(self, g: jnp.ndarray, topo):
        """Single shard_map-wrapped iteration (pad -> exchange -> kernels
        -> unpad), on the non-overlapped schedule. ``fix_loop`` is the
        production path — it amortizes the topology exchange and the
        shard_map entry over all iterations."""
        be = self.bind()
        plan = plan_blocks(g.shape, be.mesh, be.axis_name)
        g_p = _pad_blocks(g, plan)
        topo_p = jax.tree_util.tree_map(lambda x: _pad_blocks(x, plan), topo)

        def spmd(g_loc, topo_loc):
            topo_ext = exchange_tree(topo_loc, plan, 1)
            g2, viol, _, _, _ = _step_plain(
                g_loc, topo_ext, jnp.bool_(True), jnp.int32(0), (),
                plan=plan, interpret=be._interpret(), worklist=False)
            return g2, viol, _moved_count(g2, g_loc, plan)

        spec = plan.spec()
        g2, viol, moved = jax.shard_map(
            spmd, mesh=be.mesh, in_specs=(spec, spec),
            out_specs=(spec, PartitionSpec(), PartitionSpec()),
            check_vma=False)(g_p, topo_p)
        return _unpad(g2, plan), viol, moved

    # -- full-loop fast path consumed by fixes.fused_fix ---------------
    def fix_loop(self, g0: jnp.ndarray, topo,
                 max_iters: Optional[int] = None):
        """The whole fused loop inside ONE shard_map (one topology
        halo exchange, per-iteration face exchanges): (g, iters,
        converged), bitwise equal to the single-device loop."""
        be = self.bind()
        return sharded_fix(g0, topo, be.mesh, max_iters=max_iters,
                           axis_name=be.axis_name,
                           interpret=be._interpret(),
                           worklist=be.worklist, overlap=be.overlap)

    # -- device-resident base transform (DESIGN.md §4) ------------------
    def transform(self, f: jnp.ndarray, step) -> jnp.ndarray:
        """Quantize + Lorenzo, each device on its own block (one
        backward face exchange per sharded axis)."""
        be = self.bind()
        return sharded_transform(f, step, be.mesh, axis_name=be.axis_name,
                                 interpret=be._interpret())

    def reconstruct(self, r: jnp.ndarray, step, dtype) -> jnp.ndarray:
        """f_hat from residual codes: local cumsums + all_gather
        exclusive prefixes along every sharded axis; bitwise equal to
        the host codec's reconstruction."""
        be = self.bind()
        return sharded_reconstruct(r, step, dtype, be.mesh,
                                   axis_name=be.axis_name)

    # -- device-resident decompression path (DESIGN.md §5) --------------
    def scatter_edits(self, f_hat: jnp.ndarray, idx, val) -> jnp.ndarray:
        """Edit scatter-add with the replicated edit stream filtered
        to each device's block (zero collectives)."""
        be = self.bind()
        return sharded_scatter_edits(f_hat, idx, val, be.mesh,
                                     axis_name=be.axis_name)

    # -- observability (DESIGN.md §9) ------------------------------------
    def halo_plan(self, shape: Tuple[int, ...], dtype) -> Dict[str, int]:
        """Per-mesh-axis halo bytes of one fix iteration for a field of
        ``shape``/``dtype`` under this backend's schedule flags."""
        be = self.bind()
        return halo_plan(shape, dtype, be.mesh, axis_name=be.axis_name,
                         overlap=be.overlap, worklist=be.worklist)

    # -- on-device entropy codec (DESIGN.md §8) --------------------------
    def pack_codes(self, r: jnp.ndarray):
        """Chunked-bitplane pack on the global code array. Every
        per-chunk stage (zigzag, plane transpose, width reduction) is
        chunk-independent and the offset scan/compaction are one
        XLA scan + scatter, so GSPMD partitions the jnp codec across
        the mesh without bespoke collectives — and the packed stream
        stays bitwise identical to every other backend's."""
        from ..kernels.pack import pack_codes_jnp
        return pack_codes_jnp(r)

    def unpack_codes(self, words, bits, shape: Tuple[int, ...]
                     ) -> jnp.ndarray:
        """Inverse of ``pack_codes`` on global arrays (same GSPMD
        argument as ``pack_codes``)."""
        from ..kernels.pack import unpack_codes_jnp
        return unpack_codes_jnp(words, bits, tuple(shape))


register_backend(ShardedBackend())
