"""End-to-end driver #1 (the paper's kind): a compression sweep —
datasets x base compressors x error bounds, verify exact MSS
preservation on every cell, and print the paper's metrics (OCR, OBR, edit
ratio, PSNR, right-labeled ratio before correction).

  PYTHONPATH=src python examples/topo_pipeline.py [--full] [--stream]

Both directions default to the DEVICE-RESIDENT paths (DESIGN.md §4/§5);
every flag combination below produces bitwise-identical artifacts and
outputs — the flags change execution strategy only.

  --full           paper-scale dataset sizes and the full bound sweep
  --backend B      stencil backend for the fix loops
                   (auto | reference | pallas | pallas_tiled | sharded)
  --devices N      slab-shard fix loops/transforms over an N-device
                   ('data',) mesh: N real chips on an accelerator host,
                   N emulated devices under JAX_PLATFORMS=cpu
  --host-path      force the host byte-codec COMPRESS path (default:
                   device-resident whenever preconditions hold)
  --decode-path P  decompression path: auto | host | device
  --stream         route each dataset's szlike cells through the
                   streaming scheduler (repro.compress.stream,
                   DESIGN.md §6) instead of one-shot calls, and print
                   its stats line; artifacts stay byte-identical
"""
import argparse
import time


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--backend", default="auto",
                    help="stencil backend for the fix loops "
                         "(auto | reference | pallas | pallas_tiled | "
                         "sharded)")
    ap.add_argument("--devices", type=int, default=0,
                    help="shard the fix loops over an N-device ('data',) "
                         "mesh (real chips; emulated under "
                         "JAX_PLATFORMS=cpu)")
    ap.add_argument("--host-path", action="store_true",
                    help="force the host byte-codec path (default: the "
                         "device-resident path whenever its preconditions "
                         "hold; artifacts are bitwise identical either way)")
    ap.add_argument("--decode-path", default="auto",
                    choices=("auto", "host", "device"),
                    help="decompression path: 'device' forces the "
                         "device-resident decode (szlike artifacts only; "
                         "zfplike rows fall back to auto), 'host' the "
                         "byte-codec loop; outputs are bitwise identical")
    ap.add_argument("--stream", action="store_true",
                    help="serve each dataset's szlike cells through the "
                         "streaming scheduler (DESIGN.md §6) instead of "
                         "one-shot calls; artifacts stay byte-identical")
    return ap.parse_args()


def main():
    args = _parse_args()
    from repro.launch.mesh import request_devices
    request_devices(args.devices)       # before jax initializes

    import numpy as np
    import jax.numpy as jnp

    from repro.compress import (compress_preserving_mss, decompress_artifact,
                                decompress_preserving_mss, overall_bit_rate,
                                overall_compression_ratio, psnr, sz_roundtrip,
                                zfp_roundtrip)
    from repro.core import segmentation_accuracy, verify_preservation
    from repro.data import synthetic_field
    from repro.launch.mesh import make_data_mesh

    mesh = None
    if args.devices > 1:
        mesh = make_data_mesh(args.devices)
        print(f"# sharding fix loops over {args.devices} "
              f"{mesh.devices.flat[0].platform} devices "
              f"(mesh axes {dict(mesh.shape)})")
    datasets = {
        "molecular": (24, 24, 12),
        "nyx": (24, 24, 24),
        "climate": (48, 96),
    }
    if args.full:
        datasets = {"molecular": (48, 48, 24), "nyx": (64, 64, 64),
                    "climate": (180, 360), "combustion": (64, 64, 64),
                    "fingering": (48, 48, 48)}
    bounds = (1e-4, 1e-3) if not args.full else (1e-5, 1e-4, 1e-3, 1e-2)

    device_path = False if args.host_path else "auto"
    stream = None
    if args.stream:
        from repro.compress import CompressStream
        stream = CompressStream(window=2 * len(bounds), max_batch=len(bounds),
                                backend=args.backend, mesh=mesh,
                                device_path=device_path)
    print(f"{'dataset':12s} {'base':8s} {'rel_xi':8s} {'raw_right%':>10s} "
          f"{'OCR':>6s} {'OBR':>6s} {'edit%':>7s} {'PSNR':>6s} {'t_fix':>6s} "
          f"{'path':6s} ok")
    for name, shape in datasets.items():
        f = synthetic_field(name, shape=shape)
        rng = float(np.ptp(f))
        for base, rt in (("szlike", sz_roundtrip), ("zfplike", zfp_roundtrip)):
            futs = None
            if stream is not None and base == "szlike":
                # every bound's request in flight at once: same-spec cells
                # coalesce into batched device dispatches
                futs = {rel: stream.submit(f, rel * rng) for rel in bounds}
            for rel in bounds:
                xi = rel * rng
                fh, _ = rt(f, xi)
                raw_acc = float(segmentation_accuracy(jnp.asarray(f),
                                                      jnp.asarray(fh)))
                art = futs[rel].result() if futs is not None else \
                    compress_preserving_mss(f, xi, base=base,
                                            backend=args.backend,
                                            mesh=mesh,
                                            device_path=device_path)
                if args.decode_path == "host":
                    g = decompress_artifact(art)
                else:
                    # 'device' forces the device decode for szlike rows;
                    # zfplike has no device reconstruct, so fall back to
                    # auto there (bitwise identical output either way)
                    dp = True if (args.decode_path == "device"
                                  and base == "szlike") else "auto"
                    g = decompress_preserving_mss(art, device_path=dp,
                                                  backend=args.backend,
                                                  mesh=mesh)
                rep = verify_preservation(f, g, xi)
                ok = rep["mss_preserved"] and rep["bound_ok"]
                print(f"{name:12s} {base:8s} {rel:<8g} {100*raw_acc:10.2f} "
                      f"{overall_compression_ratio(f, art):6.2f} "
                      f"{overall_bit_rate(f, art):6.2f} "
                      f"{100*art.edit_ratio:7.3f} {psnr(f, g):6.1f} "
                      f"{art.t_fix:6.2f} {art.path:6s} {ok}")
                assert ok, (name, base, rel)
    if stream is not None:
        st = stream.stats()
        stream.close()
        print(f"# stream: {st['completed']} cells in {st['batches']} batches, "
              f"occupancy={st['batch_occupancy']:.2f}, "
              f"{st['fields_per_sec']:.2f} fields/s")
    print("all cells preserved MSS exactly within bounds")


if __name__ == "__main__":
    main()
