"""Hydrometeor mixing ratio, standing for ISABEL's CLOUDf, PRECIPf,
QCLOUDf, QGRAUPf, QICEf, QRAINf and QSNOWf: non-negative, exact zeros
wherever there is no cloud or precipitation, and positive blobs where
there is. What it keeps of the real variables is the shape of their
values: wide plateaus of exact zeros, on which every tie is decided by
linear index, beside sharp-edged positive regions around the storm. Not
their values; no ISABEL data ships with the repo.

A |k|^-3 field is raised under a storm-centred envelope (a Gaussian in
the plane around its centre, over a vertical profile that peaks at mid
levels) and cut at the quantile that leaves a share of exact zeros drawn
from the key between 75% and 90%. What lies above the cut, in
mixing-ratio units (1e-3 kg/kg per standard deviation of the field), is
the variable. The envelope is near zero at the plane's edges and its
vertical profile is periodic, so a circular shift moves the storm
without a seam."""
import jax
import jax.numpy as jnp

from bench.fields import _spectral

#: exact-zero share of a member: drawn between these
ZERO_SHARE = (0.75, 0.90)


def storm_envelope(shape):
    """exp(-(r / 0.35)^2) over each plane (r from its centre, 1 at the
    middle of an edge), times 0.6 + 0.4 cos(2 pi (z - 1/2)) over the
    levels of a 3D field."""
    ny, nx = shape[-2:]
    y = (jnp.arange(ny, dtype=jnp.float32) - (ny - 1) / 2) / (ny / 2)
    x = (jnp.arange(nx, dtype=jnp.float32) - (nx - 1) / 2) / (nx / 2)
    env = jnp.exp(-(y[:, None] ** 2 + x[None, :] ** 2) / 0.35 ** 2)
    if len(shape) == 3:
        z = jnp.arange(shape[0], dtype=jnp.float32) / shape[0]
        prof = 0.6 + 0.4 * jnp.cos(2.0 * jnp.pi * (z - 0.5))
        env = prof[:, None, None] * env[None]
    return env


def make(key, shape):
    k_field, k_share = jax.random.split(key)
    v = _spectral(k_field, shape, -3.0) + 2.0 * storm_envelope(shape)
    share = jax.random.uniform(k_share, (), jnp.float32, *ZERO_SHARE)
    cut = jnp.quantile(v, share)
    return 1e-3 * jnp.maximum(v - cut, 0.0)
