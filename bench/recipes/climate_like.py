"""Smooth bands with embedded filaments, a copy of the program's
``repro.data.fields.climate_like`` recipe (spectrum |k|^-3 plus bands
along the first axis)."""
import jax.numpy as jnp

from bench.fields import _spectral


def make(key, shape):
    g = _spectral(key, shape, -3.0)
    bands = jnp.sin(jnp.linspace(0.0, 4.0 * jnp.pi, shape[0],
                                 dtype=jnp.float32))
    return g + 0.8 * bands.reshape((shape[0],) + (1,) * (len(shape) - 1))
