"""Fields for the benchmark, made on the device from the run's seed.

Copies of the program's ``nyx_like`` and ``climate_like`` spectral
recipes (``repro.data.fields``), written in ``jax.random`` and
``jnp.fft`` so that a 512^3 pool is one jitted call on the chip instead
of float64 meshgrids and complex128 FFTs on the host. The content is not
the program's bit for bit (float32 FFTs, another generator); it has the
same spectrum and shape of values, which is what the recipes promise.

A seed of any size maps to a key through ``numpy.random.SeedSequence``,
so seeds beyond 32 bits are distinct keys too. A configuration with a
``content_seed`` keeps one content for every run and lets the run's seed
shift it (``field_pool``).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


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


def nyx_like(key: jax.Array, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Cosmology-like log-normal density (``repro.data.fields.nyx_like``)."""
    return jnp.exp(1.2 * _spectral(key, shape, -2.5))


def climate_like(key: jax.Array, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Smooth bands with embedded filaments
    (``repro.data.fields.climate_like``)."""
    g = _spectral(key, shape, -3.0)
    bands = jnp.sin(jnp.linspace(0.0, 4.0 * jnp.pi, shape[0],
                                 dtype=jnp.float32))
    return g + 0.8 * bands.reshape((shape[0],) + (1,) * (len(shape) - 1))


RECIPES: Dict[str, Callable[[jax.Array, Tuple[int, ...]], jnp.ndarray]] = {
    "nyx_like": nyx_like,
    "climate_like": climate_like,
}


@functools.partial(jax.jit, static_argnames=("recipe", "shape", "n"))
def _pool(key: jax.Array, shifts: jax.Array, recipe: str,
          shape: Tuple[int, ...], n: int) -> jnp.ndarray:
    keys = jax.random.split(key, n)
    axes = tuple(range(len(shape)))
    return jax.lax.map(
        lambda ks: jnp.roll(RECIPES[recipe](ks[0], shape), ks[1], axes),
        (keys, shifts))


def field_pool(recipe: str, shape: Sequence[int], n: int, seed: int,
               content_seed: Optional[int] = None) -> list:
    """``n`` float32 fields of ``shape`` by ``recipe``, made in one jitted
    call on the default device and returned as host arrays, since the
    program takes host arrays.

    Without ``content_seed`` the run's ``seed`` draws the fields. With it,
    ``content_seed`` draws them, the same for every run, and ``seed``
    draws a circular shift of each along every axis: a spectral field is
    periodic, so every seed then compresses the same values, with the
    same range and so the same ``xi``, in another arrangement."""
    if recipe not in RECIPES:
        raise KeyError(f"unknown field recipe {recipe!r}; known: "
                       f"{sorted(RECIPES)}")
    shape = tuple(int(s) for s in shape)
    shifts = np.zeros((int(n), len(shape)), np.int32)
    if content_seed is not None:
        rng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
        shifts = rng.integers(0, shape, size=shifts.shape).astype(np.int32)
        seed = content_seed
    pool = np.asarray(jax.device_get(
        _pool(key_for(seed, 1), jnp.asarray(shifts), recipe, shape, int(n))))
    return [pool[i] for i in range(n)]
