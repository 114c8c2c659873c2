"""End-to-end MSz-corrected compression pipeline (paper Fig. 3).

compression:   f --base compressor--> payload --decompress--> f_hat
               (f, f_hat) --C/R fix loops--> edits --codec--> edit blob
decompression: payload --> f_hat ; f_hat + edits --> g  (MSS(g) == MSS(f))

Two execution paths produce BITWISE-IDENTICAL artifacts (DESIGN.md §4):

* **device** (the production path for the szlike base): one host->device
  transfer of ``f``, then quantize+Lorenzo (``backend.transform``),
  on-device reconstruction of ``f_hat`` from the residual codes
  (``backend.reconstruct``), the fused fix loop, and on-device edit
  extraction (mask/count/compaction inside jit) — one device->host
  transfer of the int32 residual codes, after which only entropy coding
  (szlike._pack_residuals, codec.encode_edits) runs host-side.
* **host**: the original per-member byte-codec loop (any base
  compressor, any dtype, no int32 range precondition).

``device_path="auto"`` picks the device path whenever its preconditions
hold (szlike base, fused mode, f32 field — or f64 under jax x64 — and
szlike.check_int32_range passes); artifacts record which path produced
them (``CompressedArtifact.path``, header version 2).

``compress_preserving_mss_batch`` runs many same-shape fields through
ONE vmapped transform and ONE batched fix loop instead of B sequential
host codec calls.

The base codec is pluggable (``codec="szlike" | "zfplike"``, or any
codec registered through ``compress.preserve``): edit derivation is
codec-agnostic (DESIGN.md §11), the artifact records the base codec and
its payload magic, and the read side negotiates the decoder from the
magic — retired blob formats are refused, never misdecoded.

The READ side is symmetric (DESIGN.md §5): ``decompress_preserving_mss``
host-decodes the entropy streams once, then does one h2d of the int32
residual codes, on-device ``backend.reconstruct`` + edit scatter-add
(``backend.scatter_edits``), and one d2h of g — bitwise identical to the
host-side ``decompress_artifact``. ``decompress_artifact_batch`` serves
many same-shape artifacts pipelined: threaded entropy decode overlapping
per-member async device dispatch, one d2h of the stacked batch.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, List, Literal, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core import fixes
from ..core.backend import BackendLike, resolve_backend
from ..core.driver import apply_edits, extract_edits
from . import codec, preserve, szlike
from .preserve import ARTIFACT_VERSION, CompressedArtifact

BaseName = Literal["szlike", "zfplike"]
DevicePath = Union[bool, Literal["auto"]]

# compatibility aliases: the checked edit encoders moved to the
# codec-agnostic preserve layer (both paths still share them)
_encode_edits_checked = preserve.encode_edits_checked
_encode_edits_checked_dev = preserve.encode_edits_checked_dev


# test seam: when set, called as hook(direction, nbytes) for every
# host<->device ARRAY crossing the device path makes ("h2d"/"d2h";
# scalar syncs — counts, convergence flags — are not array transfers and
# are exempt). tests/test_device_path.py counts field-sized crossings.
_transfer_hook: Optional[Callable[[str, int], None]] = None


def _h2d(x: np.ndarray) -> jnp.ndarray:
    """The audited host->device seam: every array the device path
    uploads crosses here, counted by ``_transfer_hook`` and moved via
    the EXPLICIT ``jax.device_put`` — so ``debug.guards.no_transfers``
    (which disallows only implicit crossings) passes tracked uploads
    and fails untracked ones."""
    if _transfer_hook is not None:
        _transfer_hook("h2d", x.nbytes)
    with TraceAnnotation("msz.h2d", nbytes=x.nbytes):
        return jax.device_put(x)   # mszlint: disable=transfer-discipline — the choke point itself


def _d2h(x: jnp.ndarray) -> np.ndarray:
    """The audited device->host seam (explicit ``jax.device_get``);
    twin of ``_h2d``. Two profiler spans: ``msz.wait`` while the host
    blocks on the device computing ``x``, then ``msz.d2h`` for the
    transfer alone. ``device_get`` would block anyway, so the split adds
    no synchronisation."""
    if _transfer_hook is not None:
        _transfer_hook("d2h", x.nbytes)
    with TraceAnnotation("msz.wait"):
        jax.block_until_ready(x)
    with TraceAnnotation("msz.d2h", nbytes=x.nbytes):
        return jax.device_get(x)   # mszlint: disable=transfer-discipline — the choke point itself


# ---------------------------------------------------------------------------
# path selection
# ---------------------------------------------------------------------------

def _device_dtype_ok(dtype) -> bool:
    if dtype == np.float32:
        return True
    if dtype == np.float64:
        return bool(jax.config.jax_enable_x64)
    return False


def _device_path_reason(f: np.ndarray, xi: float, base: str, mode: str
                        ) -> Tuple[Optional[str], Optional[float]]:
    """(None, step) when the device path can serve this call, else
    (why not, None). One field scan total: max|f| feeds both the step
    headroom and the range-precondition check; the profiler span
    ``msz.precheck`` covers that host scan."""
    if base != "szlike":
        return (f"device path serves the szlike base only (got {base!r}); "
                "zfplike's block transform stays host-side"), None
    if mode != "fused":
        return f"device path requires mode='fused' (got {mode!r})", None
    if f.ndim not in (2, 3) or f.size == 0:
        return (f"device path needs a non-empty 2D/3D field "
                f"(shape {f.shape})"), None
    if not _device_dtype_ok(f.dtype):
        return (f"device path needs float32 (or float64 under jax x64 "
                f"mode); got {f.dtype}"), None
    with TraceAnnotation("msz.precheck"):
        amax = float(np.max(np.abs(f)))
        step = szlike.effective_step(f, xi, amax=amax)
        try:
            szlike.check_int32_range(f, step / 2.0, amax=amax)
        except ValueError as e:
            return str(e), None
    return None, step


def _resolve_device_path(device_path: DevicePath, f: np.ndarray, xi: float,
                         base: str, mode: str) -> Optional[float]:
    """The quantization step when the device path should run, else None."""
    if device_path is False:
        return None
    reason, step = _device_path_reason(f, xi, base, mode)
    if device_path is True and reason is not None:
        raise ValueError(f"device_path=True but {reason}")
    return step


# ---------------------------------------------------------------------------
# the device-resident path (DESIGN.md §4)
# ---------------------------------------------------------------------------

def _device_pack_ok(be, entropy: str) -> bool:
    """Whether ``entropy`` coding itself can run on device: device-pack
    selected and the backend implements the pack protocol entries."""
    return entropy == "device-pack" and hasattr(be, "pack_codes")


def _pull_packed(be, r) -> Tuple[np.ndarray, np.ndarray]:
    """Entropy-code one member's int32 residual codes on device and pull
    ``(words, bits)``: the chunked-bitplane stream replaces the full
    code array on the d2h hop, and no host entropy work remains — the
    blob assembly in ``sz_encode_packed`` is pure byte copying. The
    ``n_words`` sync is a scalar (exempt from the transfer-hook array
    accounting), needed to slice the jit-static capacity buffer to the
    true stream before it crosses; like every other crossing it routes
    through the explicit ``_d2h`` seam so the path stays clean under
    ``no_transfers()``."""
    w, bts, n_words = be.pack_codes(r)
    nw = int(_d2h(n_words))
    return _d2h(_slice_to(w, nw)), _d2h(bts)


@functools.partial(jax.jit, static_argnames=("n",))
def _slice_to(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """``x[:n]`` jitted with a static length: an eager slice ships its
    indices to the device per call (an implicit transfer under
    ``debug.no_transfers()``); the jitted one bakes them in at trace
    time, at the same one-compile-per-distinct-length cost."""
    return x[:n]


@functools.partial(jax.jit, static_argnames=("i",))
def _member(x_b: jnp.ndarray, i: int) -> jnp.ndarray:
    """``x_b[i]`` jitted with a static index — the batch stages' member
    extraction. Same rationale as ``_slice_to``: eager integer indexing
    is a dynamic_slice whose index crosses host->device per call."""
    return x_b[i]


def _device_compress(f: np.ndarray, xi: float, be, max_iters: Optional[int],
                     edit_value_dtype: str, step: float,
                     entropy: str = "deflate") -> CompressedArtifact:
    """Single host->device transfer of f; transform, reconstruction, fix
    loop, and edit extraction stay on-device; single device->host
    transfer of the residual codes — entropy-coded on device first when
    ``entropy="device-pack"`` — for blob assembly. ``step`` comes
    pre-validated from _device_path_reason."""
    t0 = time.perf_counter()
    fj = _h2d(f)
    r = be.transform(fj, step)
    f_hat = be.reconstruct(r, step, fj.dtype)
    base_err = float(_d2h(jnp.max(jnp.abs(fj - f_hat))))
    t1 = time.perf_counter()
    if base_err > xi * (1 + 1e-6):
        raise ValueError(
            f"reconstructed data violates the error bound before editing: "
            f"max|f-f_hat|={base_err:.3g} > xi={xi:.3g}")

    topo = fixes.field_topology(fj, xi)
    g, iters, ok = fixes.fused_fix(f_hat, topo, max_iters=max_iters,
                                   backend=be)
    if not bool(_d2h(ok)):
        raise RuntimeError(fixes.NOT_CONVERGED)
    idx_d, val_d = extract_edits(f_hat, g)
    t2 = time.perf_counter()

    # ---- residual entropy coding: on device (pack) or host (DEFLATE) ----
    if _device_pack_ok(be, entropy):
        words, bits = _pull_packed(be, r)
        payload = szlike.sz_encode_packed(words, bits, f.shape, f.dtype,
                                          step)
    else:
        payload = szlike.sz_encode_residuals(_d2h(r), f.shape, f.dtype,
                                             step, entropy=entropy)
    idx = _d2h(idx_d).astype(np.int64)
    val = _d2h(val_d)
    blob = _encode_edits_checked_dev(fj, f_hat, idx, val, xi,
                                     edit_value_dtype)
    t3 = time.perf_counter()
    return CompressedArtifact(
        base="szlike", base_payload=payload, edit_payload=blob,
        shape=f.shape, dtype=str(f.dtype), xi=xi,
        t_base=(t1 - t0) + (t3 - t2), t_fix=t2 - t1,
        edit_ratio=float(idx.size) / float(f.size),
        fix_iters=int(_d2h(iters)), backend=be.name,
        path="device", t_transform=t1 - t0, entropy=entropy,
        base_magic=preserve.payload_magic(payload).decode("ascii"),
    )


@dataclasses.dataclass
class _DeviceBatch:
    """Completed device stage of one compress batch (DESIGN.md §4/§6).

    Everything up to — and including — the single d2h of the residual
    codes has run; what remains per member is host-only entropy coding
    (``_encode_batch_member``). The stream scheduler hands that stage to
    worker threads so it overlaps the NEXT batch's device dispatch;
    ``_device_compress_batch`` runs it inline for the one-shot API."""
    fields: List[np.ndarray]
    xi_arr: np.ndarray
    steps: List[float]
    f_b: jnp.ndarray             # device-resident originals (bf16 re-verify)
    fhat_b: jnp.ndarray          # device-resident reconstructions
    r_host: Optional[np.ndarray]  # residual codes pulled to host (DEFLATE)
    edits: List[Tuple[jnp.ndarray, jnp.ndarray]]  # device (idx, val) pairs
    iters_b: np.ndarray
    backend_name: str
    t_transform_each: float
    t_fix_each: float
    t_pull_each: float
    nbytes_h2d: int = 0          # array bytes crossed host->device
    nbytes_d2h: int = 0          # array bytes crossed device->host
    # device-pack batches carry per-member (words, bits) pulled off the
    # device instead of r_host; _encode_batch_member then only assembles
    # bytes — zero host entropy work
    entropy: str = "deflate"
    packed: Optional[List[Tuple[np.ndarray, np.ndarray]]] = None


def _batch_transform(fields: List[np.ndarray], xi_arr: np.ndarray, be,
                     steps: List[float], n_check: int):
    """Shared device prologue of the two batch stages: ONE h2d of the
    stacked fields + steps, the transform/reconstruct dispatch (vmapped;
    member-sequential for distributed backends, where vmap over
    shard_map is not attempted, mirroring fused_fix_batch), and the
    pre-edit bound check of the first ``n_check`` members. Returns
    (f_stack, f_b, step_b, r_b, fhat_b, base_errs)."""
    B = len(fields)
    f_stack = np.stack(fields)
    f_b = _h2d(f_stack)
    step_b = _h2d(np.asarray(steps, fields[0].dtype))
    if hasattr(be, "fix_loop"):
        r_b = jnp.stack([be.transform(_member(f_b, i), _member(step_b, i))
                         for i in range(B)])
        fhat_b = jnp.stack([be.reconstruct(_member(r_b, i),
                                           _member(step_b, i), f_b.dtype)
                            for i in range(B)])
    else:
        r_b = jax.vmap(be.transform)(f_b, step_b)
        fhat_b = jax.vmap(lambda ri, si: be.reconstruct(ri, si, f_b.dtype))(
            r_b, step_b)
    sp = tuple(range(1, f_b.ndim))
    base_errs = _d2h(jnp.max(jnp.abs(f_b - fhat_b), axis=sp))
    for i in range(n_check):
        if base_errs[i] > xi_arr[i] * (1 + 1e-6):
            raise ValueError(
                f"batch member {i}: reconstructed data violates the error "
                f"bound before editing: max|f-f_hat|={base_errs[i]:.3g} > "
                f"xi={xi_arr[i]:.3g}")
    return f_stack, f_b, step_b, r_b, fhat_b, base_errs


def _pull_batch_codes(be, r_b, B: int, entropy: str):
    """The batch's residual-code d2h hop: per-member device-packed
    streams for ``entropy="device-pack"`` (the words replace the full
    codes on the wire and no host entropy stage remains), else the raw
    stacked codes for host DEFLATE. Returns (r_host, packed, nbytes)."""
    if _device_pack_ok(be, entropy):
        packed = [_pull_packed(be, _member(r_b, i)) for i in range(B)]
        return None, packed, sum(w.nbytes + b.nbytes for w, b in packed)
    r_host = _d2h(r_b)
    return r_host, None, r_host.nbytes


def _device_batch_stage(fields: List[np.ndarray], xi_arr: np.ndarray,
                        be, max_iters: Optional[int], steps: List[float],
                        entropy: str = "deflate") -> _DeviceBatch:
    """The device-resident half of a compress batch: ONE h2d of the
    stacked fields, ONE vmapped transform + ONE batched fix loop +
    on-device edit extraction, ONE d2h of the residual codes (device-
    packed first under ``entropy="device-pack"``). ``steps`` come
    pre-validated from the caller's _device_path_reason sweep."""
    B = len(fields)
    t0 = time.perf_counter()
    f_stack, f_b, step_b, r_b, fhat_b, base_errs = _batch_transform(
        fields, xi_arr, be, steps, n_check=B)
    t1 = time.perf_counter()

    # mszlint: disable=transfer-discipline -- xi_arr is the host numpy bounds
    topos = [fixes.field_topology(_member(f_b, i), float(xi_arr[i]))
             for i in range(B)]
    topo_b = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *topos)
    g_b, iters_b, ok_b = fixes.fused_fix_batch(fhat_b, topo_b,
                                               max_iters=max_iters, backend=be)
    if not bool(_d2h(jnp.all(ok_b))):
        raise RuntimeError(fixes.NOT_CONVERGED)
    edits = [extract_edits(_member(fhat_b, i), _member(g_b, i))
             for i in range(B)]
    t2 = time.perf_counter()

    r_host, packed, nbytes_codes = _pull_batch_codes(be, r_b, B, entropy)
    t_pull = time.perf_counter() - t2
    return _DeviceBatch(
        fields=fields, xi_arr=xi_arr, steps=steps,
        f_b=f_b, fhat_b=fhat_b, r_host=r_host, edits=edits,
        iters_b=_d2h(iters_b), backend_name=be.name,
        t_transform_each=(t1 - t0) / B, t_fix_each=(t2 - t1) / B,
        t_pull_each=t_pull / B,
        nbytes_h2d=f_stack.nbytes + step_b.nbytes,
        nbytes_d2h=nbytes_codes + base_errs.nbytes,
        entropy=entropy, packed=packed,
    )


def _encode_batch_member(db: _DeviceBatch, i: int,
                         edit_value_dtype: str) -> CompressedArtifact:
    """Per-member tail of a device batch. DEFLATE batches entropy-code
    here (thread-safe: zlib and the edit-sized d2h pulls release the
    GIL, so the stream runs many members through worker threads while
    the scheduler dispatches the next batch's device stage); device-pack
    batches arrive already entropy-coded and only assemble bytes — cheap
    enough that the stream runs them inline on the scheduler thread."""
    fi = db.fields[i]
    # per-member entropy-coding time joins t_base so batch artifacts
    # report the same cost split as solo device-path calls
    te0 = time.perf_counter()
    if db.packed is not None:
        words, bits = db.packed[i]
        payload = szlike.sz_encode_packed(words, bits, fi.shape, fi.dtype,
                                          db.steps[i])
    else:
        payload = szlike.sz_encode_residuals(db.r_host[i], fi.shape,
                                             fi.dtype, db.steps[i],
                                             entropy=db.entropy)
    idx = _d2h(db.edits[i][0]).astype(np.int64)
    val = _d2h(db.edits[i][1])
    blob = _encode_edits_checked_dev(
        _member(db.f_b, i), _member(db.fhat_b, i), idx, val,
        # mszlint: disable=transfer-discipline -- xi_arr is host numpy
        float(db.xi_arr[i]), edit_value_dtype)
    t_entropy = time.perf_counter() - te0
    return CompressedArtifact(
        base="szlike", base_payload=payload, edit_payload=blob,
        # mszlint: disable=transfer-discipline -- xi_arr is host numpy
        shape=fi.shape, dtype=str(fi.dtype), xi=float(db.xi_arr[i]),
        t_base=db.t_transform_each + db.t_pull_each + t_entropy,
        t_fix=db.t_fix_each,
        edit_ratio=float(idx.size) / float(fi.size),
        # mszlint: disable=transfer-discipline -- iters_b was pulled by _d2h
        fix_iters=int(db.iters_b[i]), backend=db.backend_name,
        path="device", t_transform=db.t_transform_each,
        entropy=db.entropy,
        base_magic=preserve.payload_magic(payload).decode("ascii"),
    )


def _device_pipelined_stage(fields: List[np.ndarray], xi_arr: np.ndarray,
                            be, max_iters: Optional[int], steps: List[float],
                            n_real: Optional[int] = None,
                            entropy: str = "deflate") -> _DeviceBatch:
    """The stream scheduler's large-member alternative to
    ``_device_batch_stage`` (DESIGN.md §6): ONE h2d + ONE vmapped
    transform/reconstruct dispatch for the whole batch (elementwise —
    vmap amortizes its dispatch overhead at every size), but the fix
    loops run per member through the SOLO ``fixes.fused_fix``
    specialization. The batched while_loop computes every member each
    iteration until the slowest converges (B x max(iters) work) and
    vmapping the interpret-mode Pallas stencils multiplies per-iteration
    cost, so above a few thousand voxels per member solo loops win;
    per-member g is the exact one-shot computation, so artifacts stay
    byte-identical. ``n_real``: members beyond it are batch padding —
    transformed (they ride the vmapped dispatch) but never fixed."""
    B = len(fields)
    n_real = B if n_real is None else n_real
    t0 = time.perf_counter()
    f_stack, f_b, step_b, r_b, fhat_b, base_errs = _batch_transform(
        fields, xi_arr, be, steps, n_check=n_real)
    t1 = time.perf_counter()

    edits: List[Tuple[jnp.ndarray, jnp.ndarray]] = []
    iters_list: List[int] = []
    for i in range(n_real):
        fhat_i = _member(fhat_b, i)
        # mszlint: disable=transfer-discipline -- xi_arr is host numpy
        topo = fixes.field_topology(_member(f_b, i), float(xi_arr[i]))
        g, iters, ok = fixes.fused_fix(fhat_i, topo, max_iters=max_iters,
                                       backend=be)
        if not bool(_d2h(ok)):
            raise RuntimeError(fixes.NOT_CONVERGED)
        edits.append(extract_edits(fhat_i, g))
        iters_list.append(int(_d2h(iters)))
    t2 = time.perf_counter()

    r_host, packed, nbytes_codes = _pull_batch_codes(be, r_b, B, entropy)
    t_pull = time.perf_counter() - t2
    empty = (jnp.zeros(0, jnp.int32), jnp.zeros(0, f_b.dtype))
    return _DeviceBatch(
        fields=fields, xi_arr=xi_arr, steps=steps,
        f_b=f_b, fhat_b=fhat_b, r_host=r_host,
        edits=edits + [empty] * (B - n_real),
        # mszlint: disable=transfer-discipline -- iters_list is python ints
        iters_b=np.asarray(iters_list + [0] * (B - n_real)),
        backend_name=be.name,
        t_transform_each=(t1 - t0) / B,
        t_fix_each=(t2 - t1) / max(n_real, 1),
        t_pull_each=t_pull / B,
        nbytes_h2d=f_stack.nbytes + step_b.nbytes,
        nbytes_d2h=nbytes_codes + base_errs.nbytes,
        entropy=entropy, packed=packed,
    )


def _device_compress_batch(fields: List[np.ndarray], xi_arr: np.ndarray,
                           be, max_iters: Optional[int], edit_value_dtype: str,
                           steps: List[float],
                           entropy: str = "deflate"
                           ) -> List[CompressedArtifact]:
    """Batch device path: ONE vmapped transform + ONE batched fix loop;
    per-member entropy coding afterwards (on device under device-pack).
    Artifacts are bitwise identical to solo device-path calls (the
    batched loop freezes early-converged members, fixes.fused_fix_batch)."""
    db = _device_batch_stage(fields, xi_arr, be, max_iters, steps,
                             entropy=entropy)
    return [_encode_batch_member(db, i, edit_value_dtype)
            for i in range(len(fields))]


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _check_base_entropy(base: str, entropy: str) -> None:
    """Validate the (base, entropy) combination: the residual entropy
    codec choice exists for the szlike residual stream only."""
    szlike.check_entropy(entropy)
    if entropy != "deflate" and base != "szlike":
        raise ValueError(
            f"entropy={entropy!r} applies to the szlike base only "
            f"(got base={base!r})")


def _host_compressor(base: str, entropy: str) -> Optional[Callable]:
    """A pre-bound compressor for the host path when ``entropy`` needs
    binding in (szlike's compressor takes the codec as a keyword), else
    None — ``preserve.compress_host`` then uses the registered default.
    The decoders dispatch on the blob magic, so no binding there."""
    if base == "szlike" and entropy != "deflate":
        return functools.partial(szlike.sz_compress, entropy=entropy)
    return None


def compress_preserving_mss(f: np.ndarray, xi: float, base: BaseName = "szlike",
                            mode: str = "fused",
                            edit_value_dtype: str = "auto",
                            max_iters: Optional[int] = None,
                            backend: BackendLike = "auto",
                            mesh=None,
                            device_path: DevicePath = "auto",
                            entropy: str = "deflate",
                            codec: Optional[str] = None
                            ) -> CompressedArtifact:
    """``codec``: the base compressor's registry name (an alias that
    overrides ``base`` when given — any codec registered through
    ``compress.preserve`` qualifies). ``mesh``: route the fix loop
    through the slab-sharded SPMD backend when the mesh has >= 2
    ``data``-axis devices. ``device_path``: run the whole compress stage
    device-resident ("auto" = whenever the preconditions hold, see
    module docstring; non-szlike bases take the codec-agnostic host
    path). ``entropy``: the szlike residual codec — "deflate" (host
    zlib, the compatibility default) or "device-pack" (the chunked-
    bitplane codec; on the device path it runs on device and the
    compress stage performs zero host entropy work). Artifacts are
    byte-for-byte identical across paths, backends, and meshes."""
    if codec is not None:
        base = codec
    f = np.asarray(f)
    _check_base_entropy(base, entropy)
    step = _resolve_device_path(device_path, f, xi, base, mode)
    if step is not None:
        be = resolve_backend(backend, f.shape, f.dtype, mesh=mesh)
        if hasattr(be, "transform"):
            return _device_compress(f, xi, be, max_iters, edit_value_dtype,
                                    step, entropy=entropy)
        if device_path is True:
            raise ValueError(
                f"device_path=True but backend {be.name!r} implements no "
                "transform/reconstruct protocol entry")

    art = preserve.compress_host(
        base, f, xi, compressor=_host_compressor(base, entropy),
        mode=mode, edit_value_dtype=edit_value_dtype, max_iters=max_iters,
        backend=backend, mesh=mesh)
    art.entropy = entropy
    return art


def compress_preserving_mss_batch(
        fields: Union[np.ndarray, Sequence[np.ndarray]],
        xi: Union[float, Sequence[float]],
        base: BaseName = "szlike",
        edit_value_dtype: str = "auto",
        max_iters: Optional[int] = None,
        backend: BackendLike = "auto",
        mesh=None,
        device_path: DevicePath = "auto",
        entropy: str = "deflate",
        codec: Optional[str] = None) -> List[CompressedArtifact]:
    """Batch variant of compress_preserving_mss for many same-shape fields.

    On the device path the base transform of ALL members runs as one
    vmapped dispatch and the fix loops as one batched while_loop
    (derive_edits_batch's machinery); host-side only the entropy coders
    run per member — and under ``entropy="device-pack"`` even those move
    on device, leaving pure byte assembly. Non-szlike bases run their
    transforms host-side but still share the ONE batched fix loop
    (``preserve.compress_host_batch``). Each member's artifact is
    bitwise identical to a solo compress_preserving_mss call; t_base /
    t_fix report the batch time split evenly across members.
    """
    if codec is not None:
        base = codec
    fields = [np.asarray(fi) for fi in fields]
    _check_base_entropy(base, entropy)
    if not fields:
        return []
    if any(fi.shape != fields[0].shape for fi in fields):
        raise ValueError("batch members must share one shape; got "
                         f"{[fi.shape for fi in fields]}")
    B = len(fields)
    xi_arr = np.broadcast_to(np.asarray(xi, np.float64), (B,))

    use_dev, steps = False, []
    if device_path is not False:
        reasons = [_device_path_reason(fi, float(xi_i), base, "fused")
                   for fi, xi_i in zip(fields, xi_arr)]
        use_dev = all(r is None for r, _ in reasons)
        steps = [s for _, s in reasons]
        if device_path is True and not use_dev:
            bad = next(r for r, _ in reasons if r is not None)
            raise ValueError(f"device_path=True but {bad}")
    if use_dev:
        be = resolve_backend(backend, fields[0].shape, fields[0].dtype,
                             mesh=mesh)
        if hasattr(be, "transform"):
            be = fixes._bind(be)
            return _device_compress_batch(fields, xi_arr, be, max_iters,
                                          edit_value_dtype, steps,
                                          entropy=entropy)
        if device_path is True:
            raise ValueError(
                f"device_path=True but backend {be.name!r} implements no "
                "transform/reconstruct protocol entry")

    arts = preserve.compress_host_batch(
        base, fields, xi_arr, compressor=_host_compressor(base, entropy),
        edit_value_dtype=edit_value_dtype, max_iters=max_iters,
        backend=backend, mesh=mesh)
    for art in arts:
        art.entropy = entropy
    return arts


def decompress_artifact(art: CompressedArtifact) -> np.ndarray:
    """Host-side decompression: magic-negotiated base decode
    (``preserve.decode_payload`` — retired blob formats are refused,
    never misdecoded) + numpy edit apply. Works for any base/dtype;
    ``decompress_preserving_mss`` is the production read path
    (device-resident whenever possible)."""
    f_hat = preserve.decode_payload(art)
    idx, val = codec.decode_edits(art.edit_payload)
    return apply_edits(f_hat, idx, val)


# ---------------------------------------------------------------------------
# the device-resident decompression path (DESIGN.md §5)
# ---------------------------------------------------------------------------

def _device_decode_reason(art: CompressedArtifact) -> Optional[str]:
    """None when the device decode path can serve ``art`` on metadata
    grounds (the residual-code range check runs after entropy decode),
    else why not. Mirrors _device_path_reason on the write side."""
    if art.base != "szlike":
        return (f"device decode serves the szlike base only (got "
                f"{art.base!r}); zfplike's block transform stays host-side")
    if len(art.shape) not in (2, 3) or _size_of(art.shape) == 0:
        return (f"device decode needs a non-empty 2D/3D field "
                f"(shape {art.shape})")
    if not _device_dtype_ok(np.dtype(art.dtype)):
        return (f"device decode needs float32 (or float64 under jax x64 "
                f"mode); got {art.dtype}")
    return None


def _size_of(shape) -> int:
    return int(np.prod(shape, dtype=np.int64)) if len(shape) else 1


def _decode_backend(backend: BackendLike, shape, dtype, mesh,
                    device_path: DevicePath):
    """Resolve the stencil backend for a decode call, or None (-> host
    fallback) when it lacks the reconstruct/scatter protocol entries."""
    be = resolve_backend(backend, shape, np.dtype(dtype), mesh=mesh)
    if hasattr(be, "reconstruct") and hasattr(be, "scatter_edits"):
        return be
    if device_path is True:
        raise ValueError(
            f"device_path=True but backend {be.name!r} implements no "
            "reconstruct/scatter_edits protocol entry")
    return None


def _checked_codes(art: CompressedArtifact):
    """Entropy-decode ``art``'s residual stream and validate the int32
    reconstruction precondition. Returns (r, shape, dtype, step) or a
    reason string. Device-path artifacts were range-checked against the
    original field at compress time; every other artifact's decoded
    stream is validated soundly (szlike.codes_fit_int32 — a cheap
    sum|r| sufficiency pass in the common case) because nothing in the
    codec itself enforces the error bound: a directly-constructed
    artifact can carry codes of any magnitude."""
    r, shape, dtype, step = szlike.sz_decode_residuals(art.base_payload)
    reason = _codes_reason(art, r)
    if reason is not None:
        return reason
    return r, shape, dtype, step


def _codes_reason(art: CompressedArtifact, r: np.ndarray) -> Optional[str]:
    if art.path != "device" and not szlike.codes_fit_int32(r):
        return ("the artifact's residual codes overflow the int32 cumsum "
                "reconstruction (host-path artifact beyond the device "
                "range precondition)")
    return None


def _device_unpack_decompress(art: CompressedArtifact,
                              backend: BackendLike, mesh,
                              device_path: DevicePath
                              ) -> Optional[np.ndarray]:
    """The zero-host-entropy read fast path (DESIGN.md §8) for device-
    path SZP1 artifacts: split the blob into (words, bits) by pointer
    arithmetic, ship them to the device, and run unpack -> reconstruct
    -> edit scatter there. Device-path artifacts were range-checked at
    compress time, so no host-side code inspection is needed. Returns
    None when it cannot serve (backend without ``unpack_codes``, or a
    non-default chunk size — the host decoder handles both)."""
    from ..kernels.pack import CHUNK
    words, bits, shape, dtype, step, chunk = \
        szlike.sz_parse_packed(art.base_payload)
    if chunk != CHUNK:
        return None
    be = _decode_backend(backend, shape, dtype, mesh, device_path)
    if be is None or not hasattr(be, "unpack_codes"):
        return None
    idx, val = codec.decode_edits(art.edit_payload)
    idx, val = _pad_pow2(idx, val, _size_of(shape))
    w_j = _h2d(np.ascontiguousarray(words))
    b_j = _h2d(np.ascontiguousarray(bits))
    f_hat = be.reconstruct(be.unpack_codes(w_j, b_j, shape), step, dtype)
    g = be.scatter_edits(f_hat, _h2d(idx.astype(np.int32)), _h2d(val))
    return _d2h(g)


def decompress_preserving_mss(art: CompressedArtifact,
                              device_path: DevicePath = "auto",
                              backend: BackendLike = "auto",
                              mesh=None) -> np.ndarray:
    """The mirror of the device-resident compress path (DESIGN.md §5):
    host-decode the entropy streams once, then ONE host->device transfer
    of the int32 residual codes, on-device ``backend.reconstruct`` of
    f_hat and scatter-add of the edit deltas (``backend.scatter_edits``),
    and ONE device->host transfer of g. Bitwise identical to
    ``decompress_artifact`` — both reconstructions share the per-dtype
    arithmetic contract (szlike module docstring) and the scatter adds
    the identical f32 deltas at unique indices.

    ``device_path="auto"`` falls back to the host path whenever the
    preconditions fail (non-szlike base, unsupported dtype, residual
    codes beyond the int32 range); ``True`` raises instead; ``False``
    is ``decompress_artifact``. ``mesh`` routes reconstruction and the
    scatter through the slab-sharded SPMD backend."""
    if device_path is False:
        return decompress_artifact(art)
    reason = _device_decode_reason(art)
    if reason is None and getattr(art, "path", "host") == "device" \
            and szlike.sz_blob_entropy(art.base_payload) == "device-pack":
        g = _device_unpack_decompress(art, backend, mesh, device_path)
        if g is not None:
            return g
    decoded = None
    if reason is None:
        decoded = _checked_codes(art)
        if isinstance(decoded, str):
            reason, decoded = decoded, None
    be = None
    if reason is None:
        r, shape, dtype, step = decoded
        be = _decode_backend(backend, shape, dtype, mesh, device_path)
    if reason is not None or be is None:
        if device_path is True:
            raise ValueError(f"device_path=True but {reason}")
        return decompress_artifact(art)

    idx, val = codec.decode_edits(art.edit_payload)
    idx, val = _pad_pow2(idx, val, _size_of(shape))
    r_j = _h2d(np.ascontiguousarray(r, np.int32))
    f_hat = be.reconstruct(r_j, step, dtype)
    g = be.scatter_edits(f_hat, _h2d(idx.astype(np.int32)), _h2d(val))
    return _d2h(g)


def _pow2_at_least(n: int) -> int:
    """Smallest power of two >= n (0 stays 0) — the shared pad-to-pow2
    bound capping jit specializations at ~log2 of the padded dimension
    (edit streams here, batch axes in the stream scheduler)."""
    return 1 << max(n - 1, 0).bit_length() if n else 0


def _pad_pow2(idx_b: np.ndarray, val_b: np.ndarray, fill_idx: int):
    """Pad the edit axis to the next power of two (fill indices drop in
    the scatter) so the jitted scatter specializes on ~log2(V) distinct
    lengths instead of one per edit count — same trick as
    driver.extract_edits on the write side."""
    L = idx_b.shape[-1]
    cap = _pow2_at_least(L)
    if cap == L:
        return idx_b, val_b
    pad = [(0, 0)] * (idx_b.ndim - 1) + [(0, cap - L)]
    return (np.pad(idx_b, pad, constant_values=fill_idx),
            np.pad(val_b, pad, constant_values=0))


def decompress_artifact_batch(arts: Sequence[CompressedArtifact],
                              device_path: DevicePath = "auto",
                              backend: BackendLike = "auto",
                              mesh=None) -> List[np.ndarray]:
    """Batch decompression of many same-shape szlike artifacts, pipelined:
    the entropy streams inflate on host worker threads while each
    already-decoded member's residual codes cross to the device (one
    member-sized h2d each) and its reconstruct + edit scatter dispatch
    asynchronously; g stays device-resident until ONE d2h of the stacked
    batch at the end. Edit streams are decoded up front and padded to a
    shared power-of-two length with out-of-range indices the scatter
    drops. Per-member output is bitwise identical to a solo
    ``decompress_preserving_mss`` / ``decompress_artifact`` call.
    Heterogeneous batches (mixed shapes, dtypes, or bases) decompress
    member-by-member instead; the sharded backend serves each member's
    reconstruct/scatter over the mesh within the same pipeline."""
    arts = list(arts)
    if not arts:
        return []
    a0 = arts[0]
    uniform = all(a.base == a0.base and a.shape == a0.shape
                  and a.dtype == a0.dtype for a in arts)
    if device_path is False or not uniform:
        return [decompress_preserving_mss(a, device_path=device_path,
                                          backend=backend, mesh=mesh)
                for a in arts]
    reason = _device_decode_reason(a0)
    be = None
    if reason is None:
        shape, dtype = tuple(a0.shape), np.dtype(a0.dtype)
        be = _decode_backend(backend, shape, dtype, mesh, device_path)
    if reason is not None or be is None:
        if device_path is True:
            raise ValueError(f"device_path=True but {reason}")
        return [decompress_artifact(a) for a in arts]

    V = _size_of(shape)
    idx_b, val_b, _ = codec.decode_edits_batch(
        [a.edit_payload for a in arts], fill_idx=V)
    idx_b, val_b = _pad_pow2(idx_b, val_b, V)
    idx_j = _h2d(idx_b.astype(np.int32))
    val_j = _h2d(val_b)
    # zero-host-entropy batch fast path (DESIGN.md §8): an all-device-
    # pack device-path batch ships each member's (words, bits) straight
    # to the device — no threaded host inflate stage to pipeline at all
    from ..kernels.pack import CHUNK
    if hasattr(be, "unpack_codes") and all(
            getattr(a, "path", "host") == "device"
            and szlike.sz_blob_entropy(a.base_payload) == "device-pack"
            for a in arts):
        parsed = [szlike.sz_parse_packed(a.base_payload) for a in arts]
        if all(p[5] == CHUNK for p in parsed):
            gs = []
            for i, (words, bits, _, _, step, _) in enumerate(parsed):
                w_j = _h2d(np.ascontiguousarray(words))
                b_j = _h2d(np.ascontiguousarray(bits))
                f_hat = be.reconstruct(
                    be.unpack_codes(w_j, b_j, shape), step, dtype)
                gs.append(be.scatter_edits(f_hat, _member(idx_j, i),
                                           _member(val_j, i)))
            g_host = _d2h(jnp.stack(gs))
            return [g_host[i] for i in range(len(arts))]
    gs = []
    for i, (r, _, _, step) in enumerate(codec.iter_decode_blobs(
            szlike.sz_decode_residuals, [a.base_payload for a in arts])):
        reason = _codes_reason(arts[i], r)
        if reason is not None:
            if device_path is True:
                raise ValueError(f"device_path=True but {reason}")
            return [decompress_artifact(a) for a in arts]
        r_j = _h2d(np.ascontiguousarray(r, np.int32))
        f_hat = be.reconstruct(r_j, step, dtype)
        gs.append(be.scatter_edits(f_hat, _member(idx_j, i),
                                   _member(val_j, i)))
    g_host = _d2h(jnp.stack(gs))
    return [g_host[i] for i in range(len(arts))]


# --- paper metrics (Section 7 / Appendix B) --------------------------------

def overall_compression_ratio(f: np.ndarray, art: CompressedArtifact) -> float:
    """OCR: original bytes / (base payload + edit payload)."""
    return f.nbytes / art.nbytes


def overall_bit_rate(f: np.ndarray, art: CompressedArtifact) -> float:
    """OBR: average bits per data point after combining data + edits."""
    return art.nbytes * 8.0 / f.size


def psnr(f: np.ndarray, g: np.ndarray) -> float:
    """PSNR normalized by the VALUE RANGE max(f) - min(f), as in the paper
    and the SZ/ZFP literature — not max|f|, which wildly inflates the
    score for fields with a large offset (a field in [1000, 1001] would
    report ~60 dB extra) and is not shift-invariant."""
    f64 = np.asarray(f, np.float64)
    mse = float(np.mean((f64 - np.asarray(g, np.float64)) ** 2))
    if mse == 0:
        return float("inf")
    rng = float(np.max(f64) - np.min(f64))
    if rng == 0:
        return float("-inf")     # constant field reconstructed with error
    return 20.0 * np.log10(rng / np.sqrt(mse))
