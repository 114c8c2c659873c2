"""Horizontal wind, standing for ISABEL's Uf and Vf: the component, along
a direction drawn from the key, of the tangential wind of a vortex
centred in each plane, plus |k|^-3 turbulence. What it keeps of the real
variables is a signed field with one strong rotating structure, a calm
eye and winds that fall off outward, and no exact zeros. Not their
values; no ISABEL data ships with the repo.

The tangential speed is V s exp((1 - s^2) / 2), s = r / R, which peaks at
V = 60 m/s at the radius R = 8% of the plane's width and is near zero at
its edges; the speed weakens with height as 0.7 + 0.3 cos(2 pi z) over
the levels of a 3D field (periodic, so a circular shift leaves no seam).
The turbulence is 15% of V per standard deviation."""
import jax
import jax.numpy as jnp

from bench.fields import _spectral

V_MAX = 60.0        # m/s
R_FRAC = 0.08       # radius of the strongest wind, of the plane's width
NOISE = 0.15        # turbulence per standard deviation, of V_MAX


def make(key, shape):
    k_field, k_dir = jax.random.split(key)
    ny, nx = shape[-2:]
    dy = (jnp.arange(ny, dtype=jnp.float32) - (ny - 1) / 2)[:, None]
    dx = (jnp.arange(nx, dtype=jnp.float32) - (nx - 1) / 2)[None, :]
    radius = R_FRAC * min(ny, nx)
    s2 = (dy * dy + dx * dx) / radius ** 2
    # speed / r, so the components need no division at the centre
    per_r = jnp.exp((1.0 - s2) / 2.0) / radius
    theta = jax.random.uniform(k_dir, (), jnp.float32, 0.0, 2.0 * jnp.pi)
    comp = per_r * (dx * jnp.sin(theta) - dy * jnp.cos(theta))
    if len(shape) == 3:
        z = jnp.arange(shape[0], dtype=jnp.float32) / shape[0]
        comp = (0.7 + 0.3 * jnp.cos(2.0 * jnp.pi * z))[:, None, None] * comp
    return V_MAX * (comp + NOISE * _spectral(k_field, shape, -3.0))
