"""The repo's default lint surface — which rule audits which files.

This is the contract map from DESIGN.md §10: each rule runs only where
its bug class can occur, so a clean ``python -m tools.mszlint src tests
benchmarks`` is a meaningful statement, not a fought-down noise floor.

* ``transfer-discipline`` audits the *device-stage* functions of the
  compress/decompress pipeline, the stream scheduler's device path, the
  distributed layer, and the kernels package (everything there is
  device-facing, so ``"*"``).
* ``sentinel-dtype`` covers the kernels package — the PR 1 ±inf padding
  bug lived in the extrema stencil, and kernels are where an untyped
  sentinel meets an f32/bf16 block.
* ``scatter-discipline`` runs repo-wide over src, tests, and benchmarks:
  a duplicate-dropping ``+=`` is wrong anywhere.
* ``lock-guard`` covers the threaded modules: the stream scheduler, the
  serve-side compression manager, calibration's process-wide caches, and
  the edit codec's shared DEFLATE pool and counters.
* ``int32-range`` / ``interpret-policy`` cover all of ``src/repro``.
"""
from __future__ import annotations

from .engine import Config

#: device-stage functions audited by transfer-discipline, per file.
#: ``("*",)`` audits every function in the file.
_TRANSFER_CHECKED = {
    "*/compress/pipeline.py": (
        "_pull_packed",
        "_device_compress",
        "_device_compress_batch",
        "_batch_transform",
        "_pull_batch_codes",
        "_device_batch_stage",
        "_encode_batch_member",
        "_device_pipelined_stage",
        "decompress_preserving_mss",
        "decompress_artifact_batch",
    ),
    "*/compress/stream.py": (
        "_run_device_stage",
        "_device_stage",
        "_pack_batch",
    ),
    # preserve.py: the codec-agnostic correction layer is host-side by
    # design except the device twin of the checked edit encoder, which
    # re-verifies lossy edit dtypes on DEVICE arrays
    "*/compress/preserve.py": (
        "encode_edits_checked_dev",
    ),
    # pack.py: only the device codec entry points — the *_host/_np
    # functions at the bottom are the host mirrors of the codec and
    # convert numpy inputs by contract (first match wins, so this entry
    # precedes the kernels glob)
    "*/kernels/pack.py": (
        "pack_codes_pallas", "unpack_codes_pallas",
        "pack_codes_jnp", "unpack_codes_jnp",
    ),
    "*/distributed/*.py": ("*",),
    "*/kernels/*.py": ("*",),
}

DEFAULT = Config(
    rule_paths={
        "transfer-discipline": (
            "*/compress/pipeline.py",
            "*/compress/preserve.py",
            "*/compress/stream.py",
            "*/distributed/*.py",
            "*/kernels/*.py",
        ),
        "sentinel-dtype": (
            "*/kernels/*.py",
        ),
        "scatter-discipline": (
            "src/*", "*/src/*",
            "tests/*", "*/tests/*",
            "benchmarks/*", "*/benchmarks/*",
        ),
        "lock-guard": (
            "*/compress/stream.py",
            "*/compress/calibrate.py",
            "*/compress/codec.py",
            "*/serve/compression.py",
        ),
        "int32-range": (
            "*/repro/*",
        ),
        "interpret-policy": (
            "*/repro/*",
        ),
    },
    transfer_check_functions=_TRANSFER_CHECKED,
)
