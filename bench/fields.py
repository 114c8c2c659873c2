"""Fields for the benchmark, made on the device from the run's seed.

A recipe is a file of its own, ``bench/recipes/<name>.py``, found by
name (``bench/lookup.py``): ``make(key, shape)`` returns one float32
field. The ``nyx_like`` and ``climate_like`` recipes copy the program's
(``repro.data.fields``) in ``jax.random`` and ``jnp.fft``, so that a
512^3 pool is one jitted call on the chip instead of float64 meshgrids
and complex128 FFTs on the host. The content is not the program's bit
for bit (float32 FFTs, another generator); it has the same spectrum and
shape of values, which is what the recipes promise. This module keeps
the spectral helpers that recipes share.

A seed of any size maps to a key through ``numpy.random.SeedSequence``,
so seeds beyond 32 bits are distinct keys too. A configuration with a
``content_seed`` keeps one content for every run and lets the run's seed
shift it (``field_pool``).
"""
from __future__ import annotations

import functools
from pathlib import Path
from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from bench import lookup


def key_for(seed: int, stream: int = 0) -> jax.Array:
    """A threefry key from an arbitrary non-negative ``seed`` and a
    stream number (different uses of one seed draw different streams)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _freq_norm(shape: Sequence[int]) -> jnp.ndarray:
    """|k| on the FFT grid, built by broadcasting (no meshgrids)."""
    k2 = jnp.zeros((1,) * len(shape), jnp.float32)
    for ax, n in enumerate(shape):
        f = jnp.fft.fftfreq(n).astype(jnp.float32)
        k2 = k2 + (f * f).reshape((1,) * ax + (n,) + (1,) * (len(shape) - ax - 1))
    return jnp.sqrt(k2)


def _spectral(key: jax.Array, shape: Tuple[int, ...], slope: float
              ) -> jnp.ndarray:
    """Gaussian random field with power spectrum |k|^slope, standardized."""
    white = jax.random.normal(key, shape, jnp.float32)
    k = _freq_norm(shape)
    amp = jnp.where(k > 0, jnp.power(jnp.maximum(k, 1e-9), slope / 2.0), 0.0)
    f = jnp.fft.ifftn(jnp.fft.fftn(white) * amp).real.astype(jnp.float32)
    return (f - f.mean()) / (f.std() + 1e-12)


@functools.partial(jax.jit, static_argnames=("makers", "shape", "n"))
def _pool(key: jax.Array, shifts: jax.Array, makers: Tuple[Callable, ...],
          shape: Tuple[int, ...], n: int) -> jnp.ndarray:
    keys = jax.random.split(key, n)
    axes = tuple(range(len(shape)))
    branches = [lambda k, make=make: make(k, shape) for make in makers]
    which = jnp.arange(n, dtype=jnp.int32) % len(makers)
    return jax.lax.map(
        lambda ks: jnp.roll(jax.lax.switch(ks[2], branches, ks[0]), ks[1],
                            axes),
        (keys, shifts, which))


def field_pool(recipe: Union[str, Sequence[str]], shape: Sequence[int],
               n: int, seed: int, content_seed: Optional[int] = None,
               base: Path = lookup.BENCH) -> list:
    """``n`` float32 fields of ``shape``, made in one jitted call on the
    default device and returned as host arrays, since the program takes
    host arrays. ``recipe`` names a file under ``base/recipes``, or is a
    list of such names, of which entry ``i % len`` makes member ``i``.

    Without ``content_seed`` the run's ``seed`` draws the fields. With it,
    ``content_seed`` draws them, the same for every run, and ``seed``
    draws a circular shift of each along every axis: a spectral field is
    periodic, so every seed then compresses the same values, with the
    same range and so the same ``xi``, in another arrangement."""
    names = [recipe] if isinstance(recipe, str) else list(recipe)
    if not names:
        raise ValueError("a configuration names at least one recipe")
    makers = tuple(lookup.find("recipes", r, base=base).make for r in names)
    shape = tuple(int(s) for s in shape)
    shifts = np.zeros((int(n), len(shape)), np.int32)
    if content_seed is not None:
        rng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
        shifts = rng.integers(0, shape, size=shifts.shape).astype(np.int32)
        seed = content_seed
    pool = np.asarray(jax.device_get(
        _pool(key_for(seed, 1), jnp.asarray(shifts), makers, shape, int(n))))
    return [pool[i] for i in range(n)]
