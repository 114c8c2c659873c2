"""Cosmology-like log-normal density, a copy of the program's
``repro.data.fields.nyx_like`` recipe (spectrum |k|^-2.5)."""
import jax.numpy as jnp

from bench.fields import _spectral


def make(key, shape):
    return jnp.exp(1.2 * _spectral(key, shape, -2.5))
