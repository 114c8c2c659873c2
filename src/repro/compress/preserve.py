"""Codec-agnostic topology correction: the ``PreservingCodec`` seam
(DESIGN.md §11).

The paper's central claim is that the MSz edit derivation needs nothing
from the base compressor beyond ``(f, xi) -> f_hat`` with
``max|f - f_hat| <= xi``: the fix loop, edit extraction, and the edit
codec never look inside the payload. This module makes that seam
explicit:

* ``PreservingCodec`` — the contract a base codec signs to become
  topology-preserving: a ``compress``/``decompress`` byte codec, its
  payload magics (first four blob bytes), and the magics of *retired*
  formats it must refuse rather than misdecode.
* a registry (``register_preserving_codec`` / ``get_preserving_codec``)
  holding the built-in ``szlike`` and ``zfplike`` codecs; the pipeline's
  ``compress_preserving_mss(codec=...)`` routes through it.
* magic negotiation (``payload_codec`` / ``check_artifact``): readers
  dispatch on the payload's leading magic, cross-checked against the
  artifact's recorded base, and REFUSE retired magics (``SZJ1``,
  ``ZFJ1``) with an explanation instead of silently reconstructing a
  different field.
* the generic host correction path (``compress_host`` /
  ``compress_host_batch``): base codec round-trip, the shared fix loop
  (``core.driver.derive_edits``), checked edit encoding, one artifact
  format — identical for every registered codec.
* edit-value dtype policy (``resolve_edit_dtype``): ``"auto"`` stores
  edit deltas in the field's own precision (f4 for f32 fields, f8 for
  f64) so edit application is exact per dtype; lossy choices (bf16, or
  f4 on an f64 field) re-verify preservation after decode and fall back
  to the exact dtype when rounding breaks it.

``CompressedArtifact`` lives here (artifact version 4 records
``base_magic``, the payload's leading magic, so readers can route
without touching the byte stream); ``compress.pipeline`` re-exports it
and adds the device-resident szlike paths on top.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Mapping, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.driver import (MszResult, apply_edits, derive_edits,
                           derive_edits_batch, verify_preservation)
from ..core.fixes import NOT_CONVERGED
from . import codec, szlike, zfplike

__all__ = [
    "ARTIFACT_VERSION", "CompressedArtifact", "PreservingCodec",
    "register_preserving_codec", "get_preserving_codec",
    "available_preserving_codecs", "payload_magic", "payload_codec",
    "check_artifact", "decode_payload", "resolve_edit_dtype",
    "exact_edit_dtype", "encode_edits_checked", "encode_edits_checked_dev",
    "compress_host", "compress_host_batch",
]

#: v4: ``base_magic`` records the payload's leading four bytes so the
#: read side can negotiate the base codec without sniffing the stream
ARTIFACT_VERSION = 4


@dataclasses.dataclass
class CompressedArtifact:
    """One MSS-preserving compression result: the base codec's payload
    plus the MSz edit blob, with the metadata both read paths need.

    On the device path ``t_fix`` ends right after the ``extract_edits``
    dispatch, which it does not wait for: the wait for edit compaction
    lands in ``t_base``, whose part after ``t_transform`` is the pull of
    the codes and edits and the host's entropy and edit coding."""
    base: str
    base_payload: bytes
    edit_payload: bytes
    shape: tuple
    dtype: str
    xi: float
    # bookkeeping for the paper's metrics
    t_base: float = 0.0          # base compressor seconds (t_comp)
    t_fix: float = 0.0           # MSz fix seconds (t_fix)
    edit_ratio: float = 0.0
    fix_iters: int = 0
    backend: str = ""            # stencil backend that ran the fix loop
    # versioned header (v2): which path produced the artifact, and the
    # device base-transform time separated out of t_base (0.0 host-side)
    version: int = ARTIFACT_VERSION
    path: str = "host"           # "host" | "device"
    t_transform: float = 0.0     # device quantize+Lorenzo+reconstruct secs
    # v3: which residual entropy codec the base payload carries
    # (szlike.ENTROPIES; redundant with the blob magic but lets readers
    # route without touching the byte stream)
    entropy: str = "deflate"     # "deflate" | "device-pack"
    # v4: the payload's leading magic (ascii, e.g. "SZJ2"/"SZP1"/"ZFJ2")
    # — the read side's codec negotiation key, cross-checked against
    # ``base`` by ``check_artifact``
    base_magic: str = ""

    @property
    def nbytes(self) -> int:
        """Total compressed bytes: base payload + edit blob."""
        return len(self.base_payload) + len(self.edit_payload)


@dataclasses.dataclass(frozen=True)
class PreservingCodec:
    """The contract a base codec signs to be topology-corrected.

    ``compress(f, xi) -> payload`` must produce a self-describing blob
    whose ``decompress(payload)`` returns ``f_hat`` in the FIELD'S dtype
    with ``max|f - f_hat| <= xi`` (the fix loop's precondition; the
    derivation re-checks it and raises on violation). ``magics`` are the
    leading four bytes of every blob format the codec reads; ``refused``
    maps RETIRED magics to the reason they must not be decoded (the read
    side raises that message instead of misdecoding). Codecs whose
    transform the stencil backends also implement on device set
    ``device_transform`` so the pipeline can route them through the
    device-resident path.
    """
    name: str
    compress: Callable[..., bytes]
    decompress: Callable[[bytes], np.ndarray]
    magics: Tuple[bytes, ...]
    refused: Mapping[bytes, str] = dataclasses.field(default_factory=dict)
    device_transform: bool = False


_REGISTRY: Dict[str, PreservingCodec] = {}


def register_preserving_codec(pc: PreservingCodec) -> PreservingCodec:
    """Register ``pc`` under its name (later registrations win, so a
    test can shadow a built-in); returns ``pc`` for chaining."""
    if not pc.magics:
        raise ValueError(f"codec {pc.name!r} declares no payload magics")
    for m in tuple(pc.magics) + tuple(pc.refused):
        if len(m) != 4:
            raise ValueError(
                f"codec {pc.name!r}: payload magic {m!r} must be 4 bytes")
    _REGISTRY[pc.name] = pc
    return pc


def get_preserving_codec(name: str) -> PreservingCodec:
    """Look up a registered codec by name; raises KeyError with the
    available names otherwise."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown preserving codec {name!r}; registered: "
            f"{available_preserving_codecs()}") from None


def available_preserving_codecs() -> Tuple[str, ...]:
    """Names of the registered preserving codecs, sorted."""
    return tuple(sorted(_REGISTRY))


register_preserving_codec(PreservingCodec(
    name="szlike",
    compress=szlike.sz_compress,
    decompress=szlike.sz_decompress,
    magics=(b"SZJ2", b"SZP1"),
    refused={b"SZJ1": (
        "SZJ1 blobs predate the shared host/device dequantization "
        "contract (f64-multiply-then-cast) and would silently "
        "reconstruct a different f_hat; re-compress with the current "
        "codec")},
    device_transform=True,
))

register_preserving_codec(PreservingCodec(
    name="zfplike",
    compress=zfplike.zfp_compress,
    decompress=zfplike.zfp_decompress,
    magics=(b"ZFJ2",),
    refused={b"ZFJ1": (
        "ZFJ1 blobs record no field dtype and always decode to float32, "
        "so an f64 artifact would silently lose the precision its error "
        "bound was derived in; re-compress with the current codec")},
))


def payload_magic(payload: bytes) -> bytes:
    """The leading four bytes of a base payload (its format magic)."""
    if len(payload) < 4:
        raise ValueError(
            f"base payload too short for a magic: {len(payload)} bytes")
    return bytes(payload[:4])


def payload_codec(payload: bytes) -> PreservingCodec:
    """Negotiate the codec that reads ``payload`` from its magic.

    Retired magics raise the registering codec's refusal message (old
    blobs are REFUSED, never misdecoded); unknown magics raise with the
    full set of readable formats."""
    magic = payload_magic(payload)
    for pc in _REGISTRY.values():
        if magic in pc.magics:
            return pc
        if magic in pc.refused:
            raise ValueError(
                f"refusing retired {magic.decode('ascii', 'replace')!r} "
                f"payload: {pc.refused[magic]}")
    known = sorted(m.decode("ascii", "replace")
                   for pc in _REGISTRY.values() for m in pc.magics)
    raise ValueError(
        f"unknown base payload magic {magic!r}; readable formats: {known}")


def check_artifact(art: CompressedArtifact) -> PreservingCodec:
    """Cross-check ``art.base`` against the payload's actual magic and
    return the codec that reads it. A mismatch means the artifact
    metadata and its byte stream disagree — corruption or a mis-assembled
    artifact — and raises instead of trusting either side."""
    pc = get_preserving_codec(art.base)
    magic = payload_magic(art.base_payload)
    if magic not in pc.magics:
        sniffed = payload_codec(art.base_payload)   # raises on retired/unknown
        raise ValueError(
            f"artifact records base={art.base!r} but its payload magic "
            f"{magic!r} belongs to codec {sniffed.name!r}")
    return pc


def decode_payload(art: CompressedArtifact) -> np.ndarray:
    """Magic-negotiated base decode of an artifact: ``f_hat`` in the
    artifact's recorded dtype. Both built-in codecs record the dtype in
    the blob; a disagreement with the artifact metadata raises."""
    pc = check_artifact(art)
    f_hat = pc.decompress(art.base_payload)
    want = np.dtype(art.dtype)
    if f_hat.dtype != want:
        raise ValueError(
            f"artifact records dtype {art.dtype} but the {pc.name!r} "
            f"payload decodes to {f_hat.dtype}")
    return f_hat


# ---------------------------------------------------------------------------
# edit-value dtype policy + checked encoding (shared by every path)
# ---------------------------------------------------------------------------

#: edit-value storage dtypes the pipeline accepts ("auto" resolves to
#: the field's exact dtype; the rest name codec.encode_edits formats)
EDIT_VALUE_DTYPES = ("auto", "f4", "f8", "bf16")


def exact_edit_dtype(field_dtype) -> str:
    """The edit-value storage dtype that round-trips the field's deltas
    bit-exactly: "f8" for f64 fields, "f4" otherwise."""
    return "f8" if np.dtype(field_dtype) == np.float64 else "f4"


def resolve_edit_dtype(edit_value_dtype: str, field_dtype) -> str:
    """Resolve the pipeline's ``edit_value_dtype`` parameter for a field:
    "auto" becomes the field's exact dtype, explicit names pass through
    (unknown names raise)."""
    if edit_value_dtype not in EDIT_VALUE_DTYPES:
        raise ValueError(
            f"unknown edit_value_dtype {edit_value_dtype!r}; expected one "
            f"of {EDIT_VALUE_DTYPES}")
    if edit_value_dtype == "auto":
        return exact_edit_dtype(field_dtype)
    return edit_value_dtype


def encode_edits_checked(f: np.ndarray, f_hat: np.ndarray, res: MszResult,
                         xi: float, edit_value_dtype: str) -> bytes:
    """Edit codec with the lossy-storage safety net (beyond-paper): any
    edit dtype that cannot represent the field's deltas exactly (bf16,
    or f4 on an f64 field) must re-verify exactness and the error bound
    after a decode round-trip; fall back to the exact dtype when
    rounding breaks either."""
    evd = resolve_edit_dtype(edit_value_dtype, f.dtype)
    blob = codec.encode_edits(res.edits_idx, res.edits_val, evd)
    if evd != exact_edit_dtype(f.dtype):
        idx2, val2 = codec.decode_edits(blob)
        g2 = apply_edits(f_hat, idx2, val2)
        v = verify_preservation(f, g2, xi)
        if not (v["mss_preserved"] and v["bound_ok"]):
            blob = codec.encode_edits(res.edits_idx, res.edits_val,
                                      exact_edit_dtype(f.dtype))
    return blob


def encode_edits_checked_dev(fj: jnp.ndarray, f_hat: jnp.ndarray,
                             idx: np.ndarray, val: np.ndarray, xi: float,
                             edit_value_dtype: str) -> bytes:
    """Device-path twin of ``encode_edits_checked``: the re-verification
    of a lossy edit dtype runs on DEVICE arrays (f_hat never visits the
    host), with the same predicate — so both paths make the same
    fallback decision and stay bitwise identical."""
    evd = resolve_edit_dtype(edit_value_dtype, f_hat.dtype)
    blob = codec.encode_edits(idx, val, evd)
    if evd != exact_edit_dtype(f_hat.dtype):
        idx2, val2 = codec.decode_edits(blob)
        delta2 = (jnp.zeros(f_hat.size, f_hat.dtype).at[idx2].add(val2)
                  .reshape(f_hat.shape))
        v = verify_preservation(fj, f_hat + delta2, xi)
        if not (v["mss_preserved"] and v["bound_ok"]):
            blob = codec.encode_edits(idx, val,
                                      exact_edit_dtype(f_hat.dtype))
    return blob


# ---------------------------------------------------------------------------
# the generic host correction path (any registered codec)
# ---------------------------------------------------------------------------

def _make_artifact(f: np.ndarray, payload: bytes, blob: bytes, xi: float,
                   base: str, res: MszResult, t_base: float,
                   t_fix: float) -> CompressedArtifact:
    return CompressedArtifact(
        base=base, base_payload=payload, edit_payload=blob,
        shape=f.shape, dtype=str(f.dtype), xi=xi,
        t_base=t_base, t_fix=t_fix,
        edit_ratio=res.edit_ratio, fix_iters=res.iters,
        backend=res.backend,
        base_magic=payload_magic(payload).decode("ascii", "replace"),
    )


def compress_host(name: str, f: np.ndarray, xi: float, *,
                  compressor: Callable[..., bytes] = None,
                  mode: str = "fused", edit_value_dtype: str = "auto",
                  max_iters: Optional[int] = None, backend="auto",
                  mesh=None) -> CompressedArtifact:
    """The codec-agnostic host compression path: base round-trip through
    the registered codec ``name`` (or ``compressor``, a pre-bound
    variant of it — e.g. szlike with a non-default entropy codec), the
    shared fix loop (``core.driver.derive_edits``), checked edit
    encoding, one artifact format. Everything after the base round-trip
    is identical for every codec — the PreservingCodec seam."""
    pc = get_preserving_codec(name)
    f = np.asarray(f)
    comp = compressor if compressor is not None else pc.compress
    t0 = time.perf_counter()
    payload = comp(f, xi)
    f_hat = pc.decompress(payload)
    t1 = time.perf_counter()
    res = derive_edits(f, f_hat, xi, mode=mode, max_iters=max_iters,
                       backend=backend, mesh=mesh)
    if not res.converged:
        raise RuntimeError(NOT_CONVERGED)
    t2 = time.perf_counter()
    blob = encode_edits_checked(f, f_hat, res, xi, edit_value_dtype)
    return _make_artifact(f, payload, blob, xi, pc.name, res, t1 - t0,
                          t2 - t1)


def compress_host_batch(name: str, fields: List[np.ndarray],
                        xi_arr: np.ndarray, *,
                        compressor: Callable[..., bytes] = None,
                        edit_value_dtype: str = "auto",
                        max_iters: Optional[int] = None, backend="auto",
                        mesh=None) -> List[CompressedArtifact]:
    """Batch form of ``compress_host``: per-member base round-trips, then
    ONE batched fix loop over the stacked members
    (``core.driver.derive_edits_batch``) — the same machinery the szlike
    device batch rides, so zfplike batches share the vmapped fix loop
    even though their transform stays host-side. Per-member artifacts are
    bitwise identical to solo ``compress_host`` calls."""
    pc = get_preserving_codec(name)
    comp = compressor if compressor is not None else pc.compress
    payloads, fhats, t_bases = [], [], []
    for fi, xi_i in zip(fields, xi_arr):
        t0 = time.perf_counter()
        payload = comp(fi, float(xi_i))
        fhats.append(pc.decompress(payload))
        t_bases.append(time.perf_counter() - t0)
        payloads.append(payload)

    t0 = time.perf_counter()
    results = derive_edits_batch(np.stack(fields), np.stack(fhats), xi_arr,
                                 max_iters=max_iters, backend=backend,
                                 mesh=mesh)
    t_fix_each = (time.perf_counter() - t0) / max(len(fields), 1)

    arts = []
    for fi, xi_i, payload, f_hat, res, t_base in zip(
            fields, xi_arr, payloads, fhats, results, t_bases):
        if not res.converged:
            raise RuntimeError(NOT_CONVERGED)
        blob = encode_edits_checked(fi, f_hat, res, float(xi_i),
                                    edit_value_dtype)
        arts.append(_make_artifact(fi, payload, blob, float(xi_i), pc.name,
                                   res, t_base, t_fix_each))
    return arts
