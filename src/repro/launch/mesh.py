"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.

Two data-mesh families feed the sharded MSz backend
(``repro.distributed.shardfix``):

* ``make_data_mesh(n)`` — the legacy one-axis ``('data',)`` chain,
  sharding field axis 0 into Z-slabs;
* ``make_block_mesh(shape_or_auto)`` — 1/2/3-axis block meshes over the
  ``data_z``/``data_y``/``data_x`` axis names (field axes 0/1/2), either
  an explicit shape tuple or auto-factored into the most cube-like shape
  so per-block halo surface, not the full XY plane, sets the exchange
  cost (DESIGN.md §9).

On CPU hosts set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
before jax initializes to emulate N devices; across real hosts call
``init_distributed()`` first so every process sees the global device
set.
"""
from __future__ import annotations

import os
from typing import Sequence, Tuple

import jax
import numpy as np

#: mesh axis names for block meshes, outermost first; the LAST k of
#: these name a k-axis mesh so the slab axis (data_z, field axis 0) is
#: always present and data_x only appears in full 3D decompositions.
BLOCK_AXIS_ORDER = ("data_x", "data_y", "data_z")


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods = 512 chips.
    Axes: (data, model) single-pod; (pod, data, model) multi-pod. Requires
    enough (possibly host-platform placeholder) devices — see dryrun.py."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Degenerate 1x1 mesh over the local device — used by the CPU examples
    so the same pjit code paths run everywhere."""
    return jax.make_mesh((1, 1), ("data", "model"))


def request_devices(n_devices: int) -> None:
    """Prepare an ``n_devices`` mesh; call before jax initializes.

    On a CPU run (``JAX_PLATFORMS=cpu``) this asks XLA's host platform
    for ``n_devices`` emulated devices. Anywhere else the mesh takes the
    real accelerators jax finds — one per chip — and the host-platform
    flag would mean nothing, so it is left unset."""
    if n_devices <= 1:
        return
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        return
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = \
            (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()


def make_data_mesh(n_devices: int | None = None) -> jax.sharding.Mesh:
    """One-axis ('data',) mesh over the first ``n_devices`` local devices
    (default: all). This is the axis the slab-sharded MSz fix loop
    (repro.distributed.shardfix) decomposes fields over; on CPU hosts set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
    initializes to emulate N devices. For 2D/3D block decompositions use
    :func:`make_block_mesh`."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"requested a {n}-device data mesh but {len(devs)} device(s) "
            "are available (on CPU, request_devices() before jax "
            "initializes emulates more)")
    return jax.sharding.Mesh(np.asarray(devs[:n]), ("data",))


def factor_block_shape(n_devices: int, ndim: int = 2) -> Tuple[int, ...]:
    """Factor ``n_devices`` into the most cube-like ``ndim``-tuple
    (ascending, so the largest factor lands on the innermost ``data_z``
    slab axis): 8 -> (2, 4) or (2, 2, 2), 6 -> (2, 3), primes fall back
    to (1, ..., p). Cube-like shapes minimize total halo face area for a
    given device count — the point of block decomposition."""
    n = int(n_devices)
    if n < 1:
        raise ValueError(f"cannot factor a {n}-device block mesh")
    if ndim == 1:
        return (n,)
    # peel the divisor closest to the ndim-th root, recurse on the rest
    root = round(n ** (1.0 / ndim))
    best = 1
    for cand in range(1, n + 1):
        if n % cand:
            continue
        if abs(cand - root) < abs(best - root) or (
                abs(cand - root) == abs(best - root) and cand < best):
            best = cand
    rest = factor_block_shape(n // best, ndim - 1)
    return tuple(sorted((best,) + rest))


def init_distributed(*, coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> bool:
    """Initialize ``jax.distributed`` for multi-process block meshes.

    Call once per process before any mesh construction so
    ``jax.devices()`` spans every host and the same ``shard_map``
    program runs across processes unchanged. Arguments default to the
    standard ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
    ``JAX_PROCESS_ID`` environment (as set by launchers); returns False
    without touching jax state when neither arguments nor environment
    request a multi-process run (the single-host emulation path), True
    after a successful ``jax.distributed.initialize``. Idempotent:
    re-initialization attempts are swallowed."""
    addr = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    nproc = num_processes if num_processes is not None else (
        int(os.environ["JAX_NUM_PROCESSES"])
        if "JAX_NUM_PROCESSES" in os.environ else None)
    if addr is None or nproc is None or nproc <= 1:
        return False
    pid = process_id if process_id is not None else (
        int(os.environ["JAX_PROCESS_ID"])
        if "JAX_PROCESS_ID" in os.environ else None)
    try:
        jax.distributed.initialize(coordinator_address=addr,
                                   num_processes=nproc,
                                   process_id=pid)
    except RuntimeError:
        # already initialized (idempotent re-entry from a second caller)
        pass
    return True


def make_block_mesh(shape: Sequence[int] | str | None = "auto", *,
                    ndim: int = 2) -> jax.sharding.Mesh:
    """Block mesh for the 2D/3D block-decomposed sharded fix loop.

    ``shape`` is an explicit mesh-shape tuple — 1, 2, or 3 entries,
    outermost first, mapped onto the LAST k of ``(data_x, data_y,
    data_z)`` so a 2-tuple gives ``('data_y', 'data_z')`` (field axes
    1 and 0) and a 3-tuple the full 3D decomposition — or ``"auto"``
    (the default), which factors every available device into the most
    cube-like ``ndim``-tuple (``make_block_mesh()`` on 8 devices gives a
    (2, 4) ``('data_y', 'data_z')`` mesh; ``ndim=3`` gives (2, 2, 2)).

    Emulation: on CPU hosts set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
    initializes; across real hosts call :func:`init_distributed` first.
    """
    devs = jax.devices()
    if shape is None or (isinstance(shape, str) and shape == "auto"):
        shape_t = factor_block_shape(len(devs), ndim)
    elif isinstance(shape, str):
        raise ValueError(
            f"shape must be a tuple of mesh-axis sizes or 'auto', "
            f"got {shape!r}")
    else:
        shape_t = tuple(int(s) for s in shape)
    if not 1 <= len(shape_t) <= 3 or any(s < 1 for s in shape_t):
        raise ValueError(
            f"block mesh shape must be 1-3 positive axis sizes, "
            f"got {shape_t}")
    n = int(np.prod(shape_t))
    if n > len(devs):
        raise ValueError(
            f"requested a {shape_t} block mesh ({n} devices) but "
            f"{len(devs)} device(s) are available (set "
            "--xla_force_host_platform_device_count in XLA_FLAGS before "
            "jax initializes to emulate more, or run init_distributed() "
            "for a real multi-host mesh)")
    names = BLOCK_AXIS_ORDER[-len(shape_t):]
    arr = np.asarray(devs[:n]).reshape(shape_t)
    return jax.sharding.Mesh(arr, names)
