"""Compression-service launcher: drive ``repro.serve.compression`` with
synthetic streaming traffic and report service metrics (DESIGN.md §6).

  PYTHONPATH=src python -m repro.launch.serve --smoke
  PYTHONPATH=src python -m repro.launch.serve --fields 64 --shape 48,48,48 \
      --window 8 --max-batch 4 --stats-port 8080 --verify

Generates a stream of synthetic scalar fields (mixed shapes/bounds when
``--mixed``), submits them through a ``CompressionService`` — coalesced
into batched device dispatches, entropy coding overlapped on worker
threads — then round-trips every artifact through the decompress stream.
``--devices N`` serves stream members slab-sharded over an N-device
('data',) mesh: N real chips on an accelerator host, N emulated
devices under ``JAX_PLATFORMS=cpu``; ``--stats-port P`` exposes the
live stats document at ``http://127.0.0.1:P/stats`` while the run is in
flight. ``--verify`` checks exact MSS preservation and byte-identity
against the one-shot pipeline on every request.

The LM serving launcher this module used to hold lives at
``repro.launch.serve_lm``.
"""
from __future__ import annotations

import argparse
import time


def _parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fields", type=int, default=16,
                    help="number of fields in the synthetic request stream")
    ap.add_argument("--shape", default="24,24,24",
                    help="comma-separated field shape (2D or 3D)")
    ap.add_argument("--xi-rel", type=float, default=1e-3,
                    help="error bound as a fraction of each field's range")
    ap.add_argument("--window", type=int, default=8,
                    help="in-flight request bound (backpressure window)")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="dynamic-batching limit per device dispatch")
    ap.add_argument("--coalesce-ms", type=float, default=2.0,
                    help="linger for batch stragglers before dispatching")
    ap.add_argument("--backend", default="auto",
                    help="stencil backend (auto | reference | pallas | "
                         "pallas_tiled | sharded)")
    ap.add_argument("--devices", type=int, default=0,
                    help="serve stream members slab-sharded over an "
                         "N-device ('data',) mesh (real chips; emulated "
                         "under JAX_PLATFORMS=cpu)")
    ap.add_argument("--mixed", action="store_true",
                    help="mix a second field shape and per-request bounds "
                         "into the traffic (exercises per-spec batching)")
    ap.add_argument("--stats-port", type=int, default=0,
                    help="serve GET /stats JSON on this port while running "
                         "(0 = no HTTP endpoint)")
    ap.add_argument("--verify", action="store_true",
                    help="verify MSS preservation + byte-identity vs the "
                         "one-shot pipeline on every request")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny quick-run preset (implies --verify)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse_args(argv)
    from repro.launch.mesh import request_devices
    request_devices(args.devices)       # before jax initializes
    if args.smoke:
        args.fields = min(args.fields, 8)
        args.shape = "12,12,12"
        args.verify = True

    import numpy as np

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()

    from repro.compress import compress_preserving_mss
    from repro.core import verify_preservation
    from repro.data import synthetic_field
    from repro.launch.mesh import make_data_mesh
    from repro.serve import CompressionService, ServiceConfig
    from repro.serve.compression import start_stats_server

    shape = tuple(int(s) for s in args.shape.split(","))
    mesh = None
    if args.devices > 1:
        mesh = make_data_mesh(args.devices)
        print(f"# serving over {args.devices} "
              f"{mesh.devices.flat[0].platform} devices "
              f"(mesh axes {dict(mesh.shape)})")

    shapes = [shape] * args.fields
    if args.mixed:
        alt = tuple(max(s // 2, 8) for s in shape)
        shapes = [shape if i % 3 else alt for i in range(args.fields)]
    rng = np.random.default_rng(args.seed)
    fields = [synthetic_field("nyx", shape=sh, seed=int(rng.integers(1 << 30)))
              .astype(np.float32) for sh in shapes]
    xis = [args.xi_rel * float(np.ptp(f)) for f in fields]
    if args.mixed:
        xis = [x * (0.5 if i % 2 else 1.0) for i, x in enumerate(xis)]

    cfg = ServiceConfig(window=args.window, max_batch=args.max_batch,
                        coalesce_ms=args.coalesce_ms, backend=args.backend,
                        mesh=mesh)
    with CompressionService(cfg) as service:
        server = None
        if args.stats_port:
            server = start_stats_server(service, port=args.stats_port)
            host, port = server.server_address[:2]
            print(f"# stats endpoint: http://{host}:{port}/stats")

        t0 = time.perf_counter()
        comp_futs = [service.submit_compress(f, xi)
                     for f, xi in zip(fields, xis)]
        arts = [fut.result() for fut in comp_futs]
        t_comp = time.perf_counter() - t0

        t0 = time.perf_counter()
        dec_futs = [service.submit_decompress(a) for a in arts]
        outs = [fut.result() for fut in dec_futs]
        t_dec = time.perf_counter() - t0

        if args.verify:
            for f, xi, art, g in zip(fields, xis, arts, outs):
                solo = compress_preserving_mss(f, xi)
                assert art.base_payload == solo.base_payload \
                    and art.edit_payload == solo.edit_payload, \
                    "service artifact differs from the one-shot pipeline"
                rep = verify_preservation(f, g, xi)
                assert rep["mss_preserved"] and rep["bound_ok"], rep
            print(f"# verified: {len(arts)} artifacts byte-identical to the "
                  "one-shot path, MSS preserved on every request")

        st = service.stats()
        for leg, dt in (("compress", t_comp), ("decompress", t_dec)):
            s = st[leg]
            print(f"{leg:10s} {args.fields / dt:8.2f} fields/s  "
                  f"batches={s['batches']:3d}  "
                  f"occupancy={s['batch_occupancy']:.2f}  "
                  f"max_in_flight={s['max_in_flight']}  "
                  f"h2d={s['nbytes_h2d']}B d2h={s['nbytes_d2h']}B  "
                  f"cache={s['cache']['hits']}h/{s['cache']['misses']}m")
        if server is not None:
            server.shutdown()
    print("OK")
    return arts


if __name__ == "__main__":
    main()
