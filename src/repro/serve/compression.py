"""repro.serve.compression — the request-batched topology-preserving
compression service (DESIGN.md §6).

``CompressionService`` is the layer the ROADMAP's "serve heavy traffic"
north star asks for on top of the streaming scheduler
(``repro.compress.stream``): concurrent callers submit compress and
decompress requests with per-request error bounds (``xi``) and base
codec selection; the service coalesces same-shape/same-dtype requests
inside a bounded window into batched device dispatches, applies
backpressure when the window fills (block or reject, per config), and
exposes a stats surface — fields/sec, batch occupancy, transfer bytes,
cache hit rates — as a dict and, via ``start_stats_server``, as a
plain-HTTP JSON endpoint.

Requests are served by the same pipeline the one-shot API uses, so every
artifact and every decompressed field is byte-identical to a solo
``compress_preserving_mss`` / ``decompress_preserving_mss`` call; the
service only changes *when* work runs, never *what* it computes.

    service = CompressionService(ServiceConfig(window=16, max_batch=4))
    fut = service.submit_compress(field, xi=1e-3)
    art = fut.result()
    g = service.decompress(art)
    print(service.stats()["compress"]["fields_per_sec"])
    service.close()
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
from concurrent.futures import Future
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from ..compress import pipeline
from ..compress.stream import (CompressStream, DecompressStream,
                               StreamBackpressure)
from ..core.backend import BackendLike

__all__ = ["ServiceConfig", "ServiceOverloaded", "CompressionService",
           "start_stats_server"]


class ServiceOverloaded(RuntimeError):
    """Raised by submit calls when the in-flight window is full and the
    service runs with ``overload="reject"`` (the HTTP-429 analogue);
    ``overload="block"`` applies backpressure by waiting instead."""


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Tuning knobs of one ``CompressionService``.

    ``window``
        In-flight request bound per direction (compress / decompress).
        This is the backpressure contract: at most ``window`` requests
        hold memory at once; producers beyond it block or get
        ``ServiceOverloaded`` (see ``overload``).
    ``max_batch``
        Dynamic-batching limit: up to this many same-(shape, dtype,
        codec) requests coalesce into one batched device dispatch.
    ``coalesce_ms``
        How long a sub-full batch lingers for stragglers before
        dispatching — the service's latency/occupancy trade-off.
    ``backend`` / ``mesh`` / ``device_path`` / ``max_iters``
        Forwarded to the pipeline (see ``compress_preserving_mss``);
        a mesh with >= 2 data-axis devices serves stream members
        slab-sharded across the device mesh.
    ``workers``
        Host worker threads per stream for entropy coding/decoding
        (default: scales with ``max_batch``). Device-pack requests
        (``entropy="device-pack"``) never touch these workers — their
        entropy streams are built on the device (DESIGN.md §8).
    ``cache_size``
        LRU capacity of each stream's dispatch-spec cache
        (``repro.compress.stream.SpecCache``).
    ``pad_pow2``
        Pad coalesced batches to power-of-two member counts so the
        vmapped dispatches specialize on ~log2(window) batch sizes.
    ``fix_batching``
        ``"fused"`` runs each batch's fix loops as one batched
        while_loop, ``"pipelined"`` as per-member solo loops behind a
        shared vmapped transform; ``"auto"`` fuses members up to a
        voxel threshold (see ``CompressStream``).
    ``fused_fix_voxels``
        The "auto" policy's voxel threshold. ``None`` (default) derives
        it from the one-shot machine calibration in
        ``repro.compress.calibrate`` (cached per backend/dtype/platform;
        ``MSZ_FUSED_FIX_VOXELS`` overrides); an explicit integer pins
        it. The per-batch decisions appear under ``fix_modes`` in
        ``stats()``.
    ``overload``
        ``"block"``: submits wait for a window slot (backpressure);
        ``"reject"``: submits raise ``ServiceOverloaded`` immediately.
    """
    window: int = 16
    max_batch: int = 4
    coalesce_ms: float = 2.0
    backend: BackendLike = "auto"
    mesh: Optional[object] = None
    device_path: pipeline.DevicePath = "auto"
    max_iters: Optional[int] = None
    workers: Optional[int] = None
    cache_size: int = 32
    pad_pow2: bool = True
    fix_batching: str = "auto"
    fused_fix_voxels: Optional[int] = None
    overload: str = "block"

    def __post_init__(self):
        if self.overload not in ("block", "reject"):
            raise ValueError(
                f'overload must be "block" or "reject", got {self.overload!r}')


class CompressionService:
    """Request queue + dynamic batching + backpressure around one
    ``CompressStream`` and one ``DecompressStream`` (DESIGN.md §6).

    Thread-safe: any number of producer threads may submit concurrently;
    results arrive on ``concurrent.futures.Future``s. Close with
    ``close()`` (or use as a context manager) to drain in-flight work.
    """

    def __init__(self, config: ServiceConfig = ServiceConfig()):
        self.config = config
        kw = dict(window=config.window, max_batch=config.max_batch,
                  linger_ms=config.coalesce_ms, backend=config.backend,
                  mesh=config.mesh, device_path=config.device_path,
                  max_iters=config.max_iters, workers=config.workers,
                  cache_size=config.cache_size, pad_pow2=config.pad_pow2,
                  fix_batching=config.fix_batching,
                  fused_fix_voxels=config.fused_fix_voxels)
        self._compress = CompressStream(**kw)
        self._decompress = DecompressStream(**kw)
        self._t_start = time.perf_counter()
        self._lock = threading.Lock()
        # one-shot interior/boundary timing probe, keyed on the probed
        # (shape, dtype, mesh) class; filled by shard_timings(), which
        # the stats endpoint may hit from concurrent server threads
        self._shard_probe: Optional[tuple] = None  # guarded-by: self._lock

    # -- submission ---------------------------------------------------
    def _guard(self, submit, *args, **kw) -> Future:
        try:
            return submit(*args, block=self.config.overload == "block", **kw)
        except StreamBackpressure as exc:
            raise ServiceOverloaded(
                f"service window full ({self.config.window} in-flight "
                "requests); retry later or configure overload='block'"
            ) from exc

    def submit_compress(self, field: np.ndarray, xi: float, *,
                        base: pipeline.BaseName = "szlike",
                        edit_value_dtype: str = "auto",
                        entropy: str = "deflate",
                        codec: Optional[str] = None) -> Future:
        """Queue a field; the Future resolves to its
        ``CompressedArtifact`` (byte-identical to the one-shot call).
        ``xi``, ``base``, and ``entropy`` ("deflate" | "device-pack",
        DESIGN.md §8) are free per request — only same-(shape, dtype,
        base, entropy) requests share a batch. ``codec`` is the
        pipeline's alias for ``base`` (any name registered through
        ``compress.preserve``; overrides ``base`` when given — non-szlike
        codecs batch through the host correction path, DESIGN.md §11).
        Device-pack batches do their residual entropy coding on the
        device, bypassing the host worker pool entirely; ``stats()``
        breaks traffic down per codec under ``entropy_codecs``."""
        if codec is not None:
            base = codec
        return self._guard(self._compress.submit, field, xi, base=base,
                           edit_value_dtype=edit_value_dtype,
                           entropy=entropy)

    def submit_decompress(self, art: pipeline.CompressedArtifact) -> Future:
        """Queue an artifact; the Future resolves to the decompressed
        field g with MSS(g) == MSS(f)."""
        return self._guard(self._decompress.submit, art)

    # -- sync conveniences --------------------------------------------
    def compress(self, field: np.ndarray, xi: float, *,
                 base: pipeline.BaseName = "szlike",
                 edit_value_dtype: str = "auto",
                 entropy: str = "deflate",
                 codec: Optional[str] = None
                 ) -> pipeline.CompressedArtifact:
        """Blocking ``submit_compress(...).result()``."""
        return self.submit_compress(
            field, xi, base=base, edit_value_dtype=edit_value_dtype,
            entropy=entropy, codec=codec).result()

    def decompress(self, art: pipeline.CompressedArtifact) -> np.ndarray:
        """Blocking ``submit_decompress(...).result()``."""
        return self.submit_decompress(art).result()

    # -- observability ------------------------------------------------
    def shard_timings(self, *, refresh: bool = False
                      ) -> Optional[Dict[str, object]]:
        """Measure one sharded fix iteration's interior pass, ghost
        exchange, and full step on the last sharded request class (the
        compute/communication-overlap surface of DESIGN.md §9). Runs a
        real timed probe (compiled, synthetic data of the recorded
        shape/dtype) the first time — and again only with ``refresh`` —
        then serves the cached result; None when no sharded dispatch has
        happened yet or no data mesh is reachable."""
        shard = self._compress.stats().get("shard") or {}
        meta = shard.get("last")
        if not meta:
            return None
        from ..distributed.shardfix import active_data_mesh, time_step_parts
        mesh = self.config.mesh
        if mesh is None:
            mesh = active_data_mesh()
        if mesh is None:
            return None
        shape = tuple(meta["shape"])
        key = (shape, meta["dtype"], tuple(mesh.axis_names),
               tuple(mesh.devices.shape))
        with self._lock:
            probe = self._shard_probe
        if probe is not None and not refresh and probe[0] == key:
            return probe[1]
        from ..core import field_topology
        rng = np.random.default_rng(0)
        f = rng.normal(size=shape).astype(meta["dtype"])
        topo = field_topology(jnp.asarray(f), 0.1)
        timings = time_step_parts(jnp.asarray(f), topo, mesh)
        doc = dict(shape=list(shape), dtype=meta["dtype"], **timings)
        with self._lock:
            self._shard_probe = (key, doc)
        return doc

    def stats(self) -> Dict[str, object]:
        """The service stats document (what the HTTP endpoint serves):
        uptime plus one ``repro.compress.stream`` counter snapshot per
        direction — fields/sec, batch occupancy, in-flight depth,
        fix-loop passes (``fix_iters``, ``fix_iters_max``), transfer
        bytes, spec-cache hit/miss/eviction counts, the straggler
        policy's live coalescing scale, and per-mesh-axis
        halo-exchange bytes for sharded dispatches. ``shard_timings``
        carries the cached interior/boundary probe when one has run
        (``shard_timings()`` or ``GET /stats?probe=1`` triggers it)."""
        return dict(
            uptime_s=time.perf_counter() - self._t_start,
            config=dict(window=self.config.window,
                        max_batch=self.config.max_batch,
                        coalesce_ms=self.config.coalesce_ms,
                        overload=self.config.overload),
            compress=self._compress.stats(),
            decompress=self._decompress.stats(),
            shard_timings=self._shard_timings_snapshot(),
        )

    def _shard_timings_snapshot(self) -> Optional[Dict[str, object]]:
        with self._lock:
            return self._shard_probe[1] if self._shard_probe else None

    # -- lifecycle ----------------------------------------------------
    def flush(self) -> None:
        """Block until every in-flight request (both directions) has
        completed or failed."""
        self._compress.flush()
        self._decompress.flush()

    def close(self) -> None:
        """Drain in-flight work and stop both streams (idempotent)."""
        self._compress.close()
        self._decompress.close()

    def __enter__(self) -> "CompressionService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def start_stats_server(service: CompressionService, port: int = 0,
                       host: str = "127.0.0.1"):
    """Serve ``service.stats()`` as JSON over plain HTTP on a daemon
    thread: ``GET /stats`` returns the live stats document,
    ``GET /healthz`` returns ``ok``. Returns the running
    ``ThreadingHTTPServer`` (``.server_address`` carries the bound port
    when ``port=0``); call ``.shutdown()`` to stop it."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):              # noqa: N802 (http.server API)
            if self.path == "/healthz":
                body, ctype = b"ok\n", "text/plain"
            elif self.path.split("?")[0] in ("/", "/stats"):
                if "probe=1" in (self.path.split("?") + [""])[1]:
                    try:
                        service.shard_timings()
                    except Exception:   # noqa: BLE001 — stats must not 500
                        pass
                body = (json.dumps(service.stats(), indent=2) + "\n").encode()
                ctype = "application/json"
            else:
                self.send_error(404, "unknown path (try /stats)")
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):  # quiet: stats polls are chatty
            pass

    server = ThreadingHTTPServer((host, port), Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True,
                              name="compression-stats-http")
    thread.start()
    return server
