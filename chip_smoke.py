#!/usr/bin/env python3
"""Smoke run of the compressor's main path on the TPU.

    python chip_smoke.py               # one chip, phases a-e
    python chip_smoke.py --four-chips  # the sharded fix loop on four chips

One process drives every phase through the public entry points
(``compress_preserving_mss``, ``decompress_preserving_mss``,
``CompressionService``), with fields generated from a seed at the shapes
SDRBench publishes:

  a. device and policy: a TPU, lowered (not interpreted) Pallas
     kernels, and the ``pallas`` backend for every shape below;
  b. a Nyx-like 512^3 f32 snapshot: compress -> decompress -> verify;
  c. a CESM-like 1800x3600 f32 slice, the same round trip (2D kernels);
  d. four ISABEL-shaped 100x500x500 f32 requests through the service,
     each byte-identical to the one-shot artifact;
  e. byte-identical artifacts from the pallas, reference and host paths
     and the device-pack codec at small sizes, and the ``core.ref``
     oracle's verdict on a field decoded on the chip.

``--four-chips`` runs only the 512^3 field through the ``sharded``
backend on a (2, 2) block mesh and compares it with the one-chip
``pallas`` artifact.

Every phase prints one ``# <phase> {json}`` line; wall times there are
informational. Any failure raises, so the exit code is non-zero and no
result line is printed. The last line is the result:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

NYX_SHAPE = (512, 512, 512)        # SDRBench Nyx snapshot
CESM_SHAPE = (1800, 3600)          # SDRBench CESM-ATM 2D slice
ISABEL_SHAPE = (100, 500, 500)     # SDRBench Hurricane ISABEL variable
SERVICE_REQUESTS = 4
PARITY_SHAPES = ((48, 48, 48), (256, 256))
ORACLE_SHAPE = (24, 24, 24)
XI_REL = 1e-3                      # error bound relative to the range
SEED = 0


class SmokeFailure(AssertionError):
    """A phase's output was wrong."""


def log(phase: str, **fields) -> None:
    print(f"# {phase} {json.dumps(fields, sort_keys=True)}", flush=True)


def require(ok: bool, phase: str, what: str) -> None:
    if not ok:
        raise SmokeFailure(f"{phase}: {what}")


def field_of(name: str, shape, seed: int):
    import numpy as np
    from repro.data import synthetic_field
    f = synthetic_field(name, shape=tuple(shape), seed=seed)
    f = f.astype(np.float32)
    return f, XI_REL * float(np.ptp(f))


def same_bytes(a, b) -> bool:
    return (a.base_payload == b.base_payload
            and a.edit_payload == b.edit_payload)


def edit_count(art) -> int:
    import numpy as np
    return int(round(art.edit_ratio * int(np.prod(art.shape))))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def check_device(shapes, n_chips: int = 1) -> dict:
    """Phase a: exit unless JAX runs on TPU chips with lowered Pallas
    kernels and ``auto`` resolves to ``pallas`` for every shape."""
    import jax
    import numpy as np
    from repro.core.backend import resolve_backend
    from repro.kernels.extrema import default_interpret

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU; JAX runs on {d.platform}")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, JAX sees "
                         f"{len(devs)}")
    if "MSZ_PALLAS_INTERPRET" in os.environ:
        raise SystemExit("chip_smoke: MSZ_PALLAS_INTERPRET is set")
    if default_interpret():
        raise SystemExit("chip_smoke: Pallas kernels would run interpreted")
    for shape in shapes:
        name = resolve_backend("auto", tuple(shape), np.float32).name
        if name != "pallas":
            raise SystemExit(f"chip_smoke: backend 'auto' resolves to "
                             f"{name!r} for {tuple(shape)}, not 'pallas'")
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    log("a.device", **device)
    return device


def roundtrip(phase: str, name: str, shape, seed: int = SEED) -> dict:
    """Phases b and c: one field through compress -> decompress ->
    verify on the device path."""
    from repro.compress import (compress_preserving_mss,
                                decompress_preserving_mss)
    from repro.core import verify_preservation
    from repro.kernels.extrema import default_interpret

    f, xi = field_of(name, shape, seed)
    t0 = time.perf_counter()
    art = compress_preserving_mss(f, xi, device_path=True)
    t1 = time.perf_counter()
    g = decompress_preserving_mss(art, device_path=True)
    t2 = time.perf_counter()
    rep = verify_preservation(f, g, xi)
    t3 = time.perf_counter()
    out = dict(shape=list(shape), backend=art.backend,
               interpret=default_interpret(), path=art.path,
               fix_iters=art.fix_iters, edits=edit_count(art),
               compressed_bytes=art.nbytes,
               mss_preserved=rep["mss_preserved"], bound_ok=rep["bound_ok"],
               informational_wall_s=dict(compress=t1 - t0,
                                         decompress=t2 - t1,
                                         verify=t3 - t2))
    log(phase, **out)
    require(art.path == "device", phase, f"path {art.path!r}")
    require(art.backend == "pallas", phase, f"backend {art.backend!r}")
    require(rep["mss_preserved"], phase, "MSS not preserved")
    require(rep["bound_ok"], phase, "error bound violated")
    return out


def service(phase: str, shape, n: int = SERVICE_REQUESTS,
            seed: int = SEED) -> dict:
    """Phase d: ``n`` requests through ``CompressionService``, each
    byte-identical to the one-shot artifact and MSS-preserving."""
    from repro.compress import compress_preserving_mss
    from repro.core import verify_preservation
    from repro.kernels.extrema import default_interpret
    from repro.serve import CompressionService, ServiceConfig

    fields = [field_of("fingering", shape, seed + i) for i in range(n)]
    t0 = time.perf_counter()
    with CompressionService(ServiceConfig(max_batch=4)) as svc:
        arts = [fut.result() for fut in
                [svc.submit_compress(f, xi) for f, xi in fields]]
        t1 = time.perf_counter()
        outs = [fut.result() for fut in
                [svc.submit_decompress(a) for a in arts]]
        t2 = time.perf_counter()
        batches = svc.stats()["compress"]["batches"]
    requests = []
    for (f, xi), art, g in zip(fields, arts, outs):
        solo = compress_preserving_mss(f, xi)
        rep = verify_preservation(f, g, xi)
        requests.append(dict(
            backend=art.backend, interpret=default_interpret(),
            path=art.path, fix_iters=art.fix_iters, edits=edit_count(art),
            compressed_bytes=art.nbytes,
            same_as_one_shot=same_bytes(art, solo),
            mss_preserved=rep["mss_preserved"], bound_ok=rep["bound_ok"]))
    out = dict(shape=list(shape), requests=requests, batches=batches,
               informational_wall_s=dict(compress=t1 - t0,
                                         decompress=t2 - t1))
    log(phase, **out)
    for i, r in enumerate(requests):
        require(r["path"] == "device", phase,
                f"request {i} path {r['path']!r}")
        require(r["backend"] == "pallas", phase,
                f"request {i} backend {r['backend']!r}")
        require(r["same_as_one_shot"], phase,
                f"request {i} differs from the one-shot artifact")
        require(r["mss_preserved"] and r["bound_ok"], phase,
                f"request {i} failed verification")
    return out


def parity(phase: str, shapes=PARITY_SHAPES, seed: int = SEED) -> dict:
    """Phase e (1): pallas, reference and host-path artifacts are
    byte-identical, and so are the device-pack ones; the lowered pack
    and unpack round-trip the residual codes exactly."""
    import jax
    import numpy as np
    from repro.compress import (compress_preserving_mss,
                                decompress_preserving_mss)
    from repro.core.backend import get_backend
    from repro.kernels.pack import pack_codes_host

    out = {}
    for shape in shapes:
        f, xi = field_of("nyx" if len(shape) == 3 else "climate", shape, seed)
        runs = {}
        for entropy in ("deflate", "device-pack"):
            for label, kw in (("pallas", dict(backend="pallas",
                                               device_path=True)),
                              ("reference", dict(backend="reference",
                                                 device_path=True)),
                              ("host", dict(device_path=False))):
                runs[entropy, label] = compress_preserving_mss(
                    f, xi, entropy=entropy, **kw)
        g_pack = decompress_preserving_mss(runs["device-pack", "pallas"],
                                           device_path=True)
        g_deflate = decompress_preserving_mss(runs["deflate", "pallas"],
                                              device_path=False)
        be = get_backend("pallas")
        r = be.transform(jax.device_put(f), _step_of(f, xi))
        words, bits, n_words = be.pack_codes(r)
        n_words = int(jax.device_get(n_words))
        words = np.asarray(jax.device_get(words))[:n_words]
        bits = np.asarray(jax.device_get(bits))
        r_host = np.asarray(jax.device_get(r))
        w_ref, b_ref = pack_codes_host(r_host)
        back = np.asarray(jax.device_get(be.unpack_codes(
            jax.device_put(words), jax.device_put(bits), r_host.shape)))
        res = dict(
            deflate_identical=all(same_bytes(runs["deflate", "pallas"],
                                             runs["deflate", k])
                                  for k in ("reference", "host")),
            pack_identical=all(same_bytes(runs["device-pack", "pallas"],
                                          runs["device-pack", k])
                               for k in ("reference", "host")),
            pack_matches_host=bool(np.array_equal(words, w_ref)
                                   and np.array_equal(bits, b_ref)),
            unpack_exact=bool(np.array_equal(back, r_host)),
            decoded_identical=bool(np.array_equal(g_pack, g_deflate)),
            fix_iters=runs["deflate", "pallas"].fix_iters,
            paths=[runs["deflate", k].path
                   for k in ("pallas", "reference", "host")])
        out["x".join(map(str, shape))] = res
        for key in ("deflate_identical", "pack_identical",
                    "pack_matches_host", "unpack_exact",
                    "decoded_identical"):
            require(res[key], phase, f"{key} failed at {tuple(shape)}")
        require(res["paths"] == ["device", "device", "host"], phase,
                f"paths {res['paths']}")
    log(phase, **out)
    return out


def _step_of(f, xi: float) -> float:
    """The quantization step the device path uses for ``f``."""
    import numpy as np
    from repro.compress import szlike
    return szlike.effective_step(f, xi, amax=float(np.max(np.abs(f))))


def oracle(phase: str, shape=ORACLE_SHAPE, seed: int = SEED) -> dict:
    """Phase e (2): the pure-numpy ``core.ref`` verifier accepts a field
    compressed and decoded on the chip."""
    from repro.compress import (compress_preserving_mss,
                                decompress_preserving_mss)
    from repro.core.ref import verify_preservation_ref

    f, xi = field_of("nyx", shape, seed)
    art = compress_preserving_mss(f, xi, device_path=True)
    g = decompress_preserving_mss(art, device_path=True)
    rep = verify_preservation_ref(f, g, xi)
    out = dict(shape=list(shape), path=art.path, backend=art.backend,
               mss_preserved=rep["mss_preserved"], bound_ok=rep["bound_ok"])
    log(phase, **out)
    require(art.path == "device", phase, f"path {art.path!r}")
    require(rep["mss_preserved"] and rep["bound_ok"], phase,
            "the core.ref oracle rejects the decoded field")
    return out


def four_chips(phase: str, shape=NYX_SHAPE, seed: int = SEED,
               mesh_shape=(2, 2)) -> dict:
    """The 512^3 field through the ``sharded`` backend on a block mesh
    of four chips, against the one-chip ``pallas`` artifact. Checks
    that the fix loop's state is spread over every mesh device and that
    the compiled loop exchanges halos (``collective-permute``)."""
    import jax
    from repro.compress import compress_preserving_mss
    from repro.core import fixes
    from repro.core.backend import resolve_backend
    from repro.launch.mesh import make_block_mesh

    mesh = make_block_mesh(mesh_shape)
    n_mesh = mesh.devices.size
    f, xi = field_of("nyx", shape, seed)
    t0 = time.perf_counter()
    art_s = compress_preserving_mss(f, xi, backend="sharded", mesh=mesh,
                                    device_path=True)
    t1 = time.perf_counter()
    art_1 = compress_preserving_mss(f, xi, backend="pallas",
                                    device_path=True)
    t2 = time.perf_counter()

    # the sharded fix loop itself: where its state lives, and what the
    # compiled program exchanges
    be = resolve_backend("sharded", f.shape, f.dtype, mesh=mesh)
    fj = jax.device_put(f)
    topo = fixes.field_topology(fj, xi)
    compiled = fixes._fused_fix_impl.lower(
        fj, topo, max_iters=512, backend=be).compile()
    g, _, _ = compiled(fj, topo)
    g.block_until_ready()
    shard_devices = {s.device.id for s in g.addressable_shards}
    text = compiled.as_text()
    out = dict(shape=list(shape), mesh=list(mesh_shape),
               backend=art_s.backend, path=art_s.path,
               fix_iters=art_s.fix_iters, one_chip_fix_iters=art_1.fix_iters,
               edits=edit_count(art_s), identical=same_bytes(art_s, art_1),
               g_devices=len(g.sharding.device_set),
               g_shard_devices=len(shard_devices),
               collective_permutes=text.count("collective-permute"),
               informational_wall_s=dict(sharded=t1 - t0, one_chip=t2 - t1))
    log(phase, **out)
    require(art_s.backend == "sharded", phase, f"backend {art_s.backend!r}")
    require(out["identical"], phase,
            "sharded artifact differs from the one-chip artifact")
    require(out["g_devices"] == n_mesh and out["g_shard_devices"] == n_mesh,
            phase, f"fix-loop state on {out['g_shard_devices']} devices, "
            f"not {n_mesh}")
    require(out["collective_permutes"] > 0, phase,
            "no collective-permute in the compiled fix loop")
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 512^3 phase on four chips")
    args = ap.parse_args(argv)

    if args.four_chips:
        device = check_device([NYX_SHAPE], n_chips=4)
    else:
        device = check_device([NYX_SHAPE, CESM_SHAPE, ISABEL_SHAPE,
                               *PARITY_SHAPES, ORACLE_SHAPE])
    from repro.launch.compile_cache import configure_compile_cache
    log("cache", dir=configure_compile_cache())

    if args.four_chips:
        four_chips("four_chips", NYX_SHAPE)
    else:
        roundtrip("b.nyx", "nyx", NYX_SHAPE)
        roundtrip("c.cesm", "climate", CESM_SHAPE)
        service("d.service", ISABEL_SHAPE)
        parity("e.parity")
        oracle("e.oracle")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
