"""High-level MSz API: derive edits at compression time, apply at
decompression time, verify exact MSS preservation (the paper's Fig. 3
workflow around the C/R fix loops)."""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Literal, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import fixes, grid
from .backend import BackendLike, resolve_backend
from .labels import mss_labels


@dataclasses.dataclass
class MszResult:
    g: np.ndarray             # edited decompressed field (MSS == original's)
    edits_idx: np.ndarray     # int64 flat indices of edited vertices (sorted)
    edits_val: np.ndarray     # edit values delta_i  (g = f_hat + delta)
    iters: int                # fix-loop iterations to convergence
    converged: bool
    edit_ratio: float         # |edits| / V   (paper's 'edit ratio')
    max_abs_err: float        # max |f - g|   (must be <= xi)
    backend: str = ""         # stencil backend that executed the fix loop


Mode = Literal["fused", "paper"]


def _check_inputs(f, f_hat, xi: float):
    if f.shape != f_hat.shape:
        raise ValueError(f"shape mismatch {f.shape} vs {f_hat.shape}")
    if f.ndim not in (2, 3):
        raise ValueError("MSz operates on 2D/3D piecewise-linear scalar fields")
    if not jnp.issubdtype(f.dtype, jnp.floating):
        raise ValueError(
            f"MSz operates on floating-point fields, got dtype {f.dtype}")
    base_err = float(jnp.max(jnp.abs(f - f_hat)))
    if base_err > xi * (1 + 1e-6):
        raise ValueError(
            f"decompressed data violates the error bound before editing: "
            f"max|f-f_hat|={base_err:.3g} > xi={xi:.3g}")


def _package_result(f, f_hat, g, iters, ok, backend_name: str) -> MszResult:
    g = np.asarray(g)
    delta = g - np.asarray(f_hat)
    idx = np.flatnonzero(delta != 0.0)
    vals = delta.reshape(-1)[idx]
    return MszResult(
        g=g,
        edits_idx=idx.astype(np.int64),
        edits_val=vals,
        iters=int(iters),
        converged=bool(ok),
        edit_ratio=float(idx.size) / float(delta.size),
        max_abs_err=float(np.max(np.abs(np.asarray(f) - g))),
        backend=backend_name,
    )


def derive_edits(f, f_hat, xi: float, mode: Mode = "fused",
                 max_iters: Optional[int] = None,
                 backend: BackendLike = "auto", mesh=None) -> MszResult:
    """Compute the edit series {delta_i} such that f_hat + delta has exactly
    the MS segmentation of f, while |f - (f_hat+delta)| <= xi (Section 4).

    ``backend`` picks the stencil execution strategy for the fused loop
    ("auto" prefers the slab-sharded SPMD loop when ``mesh`` — or the
    active ``with mesh:`` context — has >= 2 data-axis devices, then the
    Pallas kernels, then the jnp reference; see core.backend). Paper mode
    always runs the reference stencils. Precondition (checked):
    |f - f_hat| <= xi, same shapes.
    """
    f = jnp.asarray(f)
    f_hat = jnp.asarray(f_hat, f.dtype)
    _check_inputs(f, f_hat, xi)

    topo = fixes.field_topology(f, xi)
    if mode == "fused":
        be = resolve_backend(backend, f.shape, f.dtype, mesh=mesh)
        g, iters, ok = fixes.fused_fix(f_hat, topo, max_iters=max_iters,
                                       backend=be)
        backend_name = be.name
    elif mode == "paper":
        g, iters, ok = fixes.paper_fix(f_hat, topo, max_iters=max_iters)
        backend_name = "reference"
    else:
        raise ValueError(f"unknown mode {mode!r}")

    return _package_result(f, f_hat, g, iters, ok, backend_name)


def derive_edits_batch(f, f_hat, xi: Union[float, Sequence[float]],
                       max_iters: Optional[int] = None,
                       backend: BackendLike = "auto",
                       mesh=None, batching: str = "auto",
                       compact_every: int = 8) -> List[MszResult]:
    """Batched ``derive_edits`` over a leading batch axis (fused mode).

    ``f``/``f_hat``: (B, *spatial) with spatial rank 2 or 3; ``xi`` is a
    scalar shared by every member or a per-member sequence of length B
    (each member's topology, and so its compaction trajectory, honors its
    own bound). The fix loops of all members run through the vmapped
    batch driver (fixes.fused_fix_batch), so many small fields pipeline
    through the stencil backend together instead of paying B sequential
    dispatches; ``batching``/``compact_every`` select its early-exit
    strategy — by default still-active members are compacted into
    power-of-two buckets every ``compact_every`` iterations, so members
    that converge early stop costing vmap lanes. Per-member results are
    bitwise identical to solo derive_edits calls under every strategy.
    """
    f = jnp.asarray(f)
    f_hat = jnp.asarray(f_hat, f.dtype)
    if f.shape != f_hat.shape:
        raise ValueError(f"shape mismatch {f.shape} vs {f_hat.shape}")
    if f.ndim not in (3, 4):
        raise ValueError(
            "derive_edits_batch expects (B, *spatial) with 2D/3D members; "
            f"got shape {f.shape}")
    B = f.shape[0]
    xi_arr = np.broadcast_to(np.asarray(xi, np.float64), (B,))
    for i in range(B):
        _check_inputs(f[i], f_hat[i], float(xi_arr[i]))

    topos = [fixes.field_topology(f[i], float(xi_arr[i])) for i in range(B)]
    topo_b = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *topos)
    be = resolve_backend(backend, f.shape[1:], f.dtype, mesh=mesh)
    g_b, iters_b, ok_b = fixes.fused_fix_batch(f_hat, topo_b,
                                               max_iters=max_iters, backend=be,
                                               batching=batching,
                                               compact_every=compact_every)
    g_b = np.asarray(g_b)
    return [_package_result(f[i], f_hat[i], g_b[i], iters_b[i], ok_b[i],
                            be.name)
            for i in range(B)]


# --- device-side edit extraction (device-resident path, DESIGN.md §4) ------

@jax.jit
def _edit_count(f_hat: jnp.ndarray, g: jnp.ndarray):
    delta = g - f_hat
    return delta, jnp.sum(delta != 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("size",))
def _edit_compact(delta: jnp.ndarray, size: int):
    flat = delta.reshape(-1)
    idx = jnp.nonzero(flat != 0, size=size, fill_value=0)[0]
    return idx, flat[idx]


@functools.partial(jax.jit, static_argnames=("n",))
def _edit_slice(idx: jnp.ndarray, val: jnp.ndarray, n: int):
    return idx[:n], val[:n]


def extract_edits(f_hat: jnp.ndarray, g: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """On-device edit extraction: ``delta != 0`` mask, count, and
    compaction all run inside jit; only the edit count crosses to the
    host (to fix the compaction's static output size), so the returned
    (idx, val) device arrays are the ONLY edit-sized data a caller needs
    to pull. Ascending flat indices — identical to the host path's
    ``np.flatnonzero`` ordering. The compaction size is rounded up to the
    next power of two (then sliced back to the true count), capping jit
    specializations at ~log2(V) instead of one per distinct edit count.

    Runs eagerly, so the count sync is an explicit ``jax.device_get``
    and the final slice runs jitted with a static length — eager
    ``int(n)`` / ``arr[:n]`` would each be an implicit transfer under
    ``debug.no_transfers()`` (eager slicing ships its indices to the
    device per call; the jitted slice bakes them in at trace time, at
    the same one-compile-per-distinct-length cost the eager op paid)."""
    delta, n = _edit_count(f_hat, g)
    n = int(jax.device_get(n))
    if n == 0:
        empty = jnp.zeros(0, jnp.int32)
        return empty, jnp.zeros(0, f_hat.dtype)
    cap = 1 << (n - 1).bit_length()
    idx, val = _edit_compact(delta, cap)
    return _edit_slice(idx, val, n)


def apply_edits(f_hat, edits_idx, edits_val) -> np.ndarray:
    """Decompression-side reconstruction: g = f_hat + delta (Fig. 3 bottom).

    Duplicate indices ACCUMULATE (``np.add.at`` semantics — buffered fancy
    ``+=`` would keep only the last value and silently drop edits). The
    codec forbids duplicates (codec.encode_edits raises), so decoded
    streams take the fast vectorized path; unsorted/duplicated inputs from
    other callers still apply every edit."""
    g = np.array(f_hat, copy=True)
    flat = g.reshape(-1)
    idx = np.asarray(edits_idx).reshape(-1)
    val = np.asarray(edits_val).reshape(-1)
    if idx.size == 0:
        return g
    if idx.size == 1 or np.all(np.diff(idx) > 0):
        # mszlint: disable=scatter-discipline -- diff>0 proves uniqueness
        flat[idx] += val            # strictly increasing => no duplicates
    else:
        np.add.at(flat, idx, val)   # unbuffered: duplicates accumulate
    return g


@jax.jit
def _scatter_edits_jit(f_hat: jnp.ndarray, idx: jnp.ndarray,
                       val: jnp.ndarray) -> jnp.ndarray:
    flat = f_hat.reshape(-1)
    flat = flat.at[idx].add(val.astype(f_hat.dtype), mode="drop")
    return flat.reshape(f_hat.shape)


def apply_edits_device(f_hat: jnp.ndarray, edits_idx, edits_val
                       ) -> jnp.ndarray:
    """On-device twin of ``apply_edits``: one jitted scatter-add, so g
    never leaves the device (the decompression path's mirror of
    ``extract_edits``; DESIGN.md §5). Indices must be unique — the codec
    invariant — making the scatter order-free and the result bitwise
    equal to the host path's ``f_hat[idx] += val``. Out-of-range indices
    (the batched path's padding rows point one past the end) are dropped,
    never wrapped, so callers can pad edit streams to a common length."""
    return _scatter_edits_jit(jnp.asarray(f_hat),
                              jnp.asarray(edits_idx, jnp.int32),
                              jnp.asarray(edits_val))


def verify_preservation(f, g, xi: float) -> dict:
    """Check both paper constraints: global error bound + exact MSS.

    Single-field only (2D/3D); a stacked batch would silently verify the
    wrong thing (labels of the batch-as-one-field), so batched artifacts
    go through ``verify_preservation_batch``."""
    f = jnp.asarray(f)
    if f.ndim not in (2, 3):
        raise ValueError(
            f"verify_preservation takes one 2D/3D field (got shape "
            f"{tuple(f.shape)}); stacked batches verify member-by-member "
            "through verify_preservation_batch")
    g = jnp.asarray(g, f.dtype)
    Mf, mf = mss_labels(f)
    Mg, mg = mss_labels(g)
    max_label_ok = bool(jnp.all(Mf == Mg))
    min_label_ok = bool(jnp.all(mf == mg))
    err = float(jnp.max(jnp.abs(f - g)))
    right = float(jnp.mean(((Mf == Mg) & (mf == mg)).astype(jnp.float32)))
    return dict(
        bound_ok=err <= xi * (1 + 1e-6),
        max_abs_err=err,
        max_labels_ok=max_label_ok,
        min_labels_ok=min_label_ok,
        mss_preserved=max_label_ok and min_label_ok,
        right_labeled_ratio=right,
    )


def verify_preservation_batch(f_b, g_b, xi) -> list:
    """Member-wise ``verify_preservation`` over stacked batches: ``f_b``
    and ``g_b`` are (B, *spatial) with 2D/3D members, ``xi`` a scalar or
    per-member sequence. Returns one verdict dict per member."""
    f_b = np.asarray(f_b)
    g_b = np.asarray(g_b)
    if f_b.ndim not in (3, 4):
        raise ValueError(
            f"verify_preservation_batch takes a (B, *spatial) stack of "
            f"2D/3D fields (got shape {f_b.shape})")
    if f_b.shape != g_b.shape:
        raise ValueError(
            f"batch shapes disagree: f {f_b.shape} vs g {g_b.shape}")
    B = f_b.shape[0]
    xi_arr = np.broadcast_to(np.asarray(xi, np.float64), (B,))
    return [verify_preservation(f_b[i], g_b[i], float(xi_arr[i]))
            for i in range(B)]
