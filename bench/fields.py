"""CESM-like 2-D fields made on the device from a seed.

A jax.random port of the three generators of ``src/repro/data/fields.py``
(its parameters and, save the ``periodic_x`` option below, its formulas;
device RNG instead of numpy's), so a run's fields cost one small jitted
program per generator and no host work:

* ``grf``: band-limited Gaussian random field (power-law spectrum).  Smooth
  at large scales with critical points spread evenly over the grid: the
  bulk of CESM-ATM's fields.
* ``vortex``: a superposition of Gaussian bumps and dips.  Large smooth,
  flat areas with few, isolated extrema and saddles: the compressible end.
  With ``periodic_x`` the bumps wrap around in x, as fields on a
  latitude-longitude grid wrap in longitude.
* ``multiscale``: GRF + vortices + white noise.  Critical points on a large
  share of the grid: the hard case for CD, RP and the restore loop.

The traffic file lists the generators and their parameters; field ``i`` of
a call uses entry ``i % len(list)``, as ``make_dataset`` cycles them.
Every field is normalized to [0, 1], so an absolute error bound is a share
of the value range.

Periodic in x matters to the compressor: it codes deltas along the
row-major order, so a block that crosses a row's end holds the step from
one row's last value to the next row's first.  On a field that does not
wrap (the numpy vortex) that step can reach half the range, so the widest
block, hence the width bucket, the pack's work and its compiled program,
depended on where the seed put the bumps.  The GRF wraps by construction.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

KINDS = ("grf", "vortex", "multiscale")


def seed_key(seed: int) -> jax.Array:
    """PRNG key from any non-negative integer seed up to 2**64: the low
    32 bits seed the key and the high bits are folded in, since
    ``jax.random.key`` keeps only 32 bits of a larger seed."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _normalize(f: jnp.ndarray) -> jnp.ndarray:
    lo, hi = f.min(), f.max()
    return ((f - lo) / jnp.maximum(hi - lo, 1e-30)).astype(jnp.float32)


def _grf(key, ny: int, nx: int, power: float) -> jnp.ndarray:
    white = jax.random.normal(key, (ny, nx), jnp.float32)
    fy = jnp.fft.fftfreq(ny)[:, None]
    fx = jnp.fft.rfftfreq(nx)[None, :]
    k = jnp.sqrt(fy * fy + fx * fx)
    amp = jnp.where(k == 0, 0.0, jnp.maximum(k, 1e-6) ** (-power / 2.0))
    f = jnp.fft.irfft2(jnp.fft.rfft2(white) * amp, s=(ny, nx))
    return _normalize(f)


def _vortex(key, ny: int, nx: int, n_vortices: int,
            periodic_x: bool = False) -> jnp.ndarray:
    """Sum of ``a * exp(-r^2 / 2s^2)`` bumps, computed as the separable
    product it is: (ny, V) x diag(a) x (V, nx), at full f32 precision.
    ``periodic_x``: x runs over [0, 1) and distances in x wrap around."""
    kc, ks, ka = jax.random.split(key, 3)
    c = jax.random.uniform(kc, (n_vortices, 2), jnp.float32)
    s = jax.random.uniform(ks, (n_vortices,), jnp.float32, 0.02, 0.12)
    a = jax.random.uniform(ka, (n_vortices,), jnp.float32, -1.0, 1.0)
    y = jnp.linspace(0.0, 1.0, ny, dtype=jnp.float32)
    if periodic_x:
        x = jnp.arange(nx, dtype=jnp.float32) / nx
    else:
        x = jnp.linspace(0.0, 1.0, nx, dtype=jnp.float32)
    dx = x[:, None] - c[None, :, 1]
    if periodic_x:
        dx = dx - jnp.round(dx)                      # nearest image
    inv = 1.0 / (2.0 * s * s)
    gy = jnp.exp(-((y[:, None] - c[None, :, 0]) ** 2) * inv[None, :])
    gx = jnp.exp(-(dx ** 2) * inv[None, :])
    f = jnp.matmul(gy * a[None, :], gx.T,
                   precision=jax.lax.Precision.HIGHEST)
    return _normalize(f)


def _multiscale(key, ny: int, nx: int, power: float, n_vortices: int,
                grf_weight: float, vortex_weight: float, noise: float,
                periodic_x: bool = False) -> jnp.ndarray:
    kg, kv, kn = jax.random.split(key, 3)
    f = (grf_weight * _grf(kg, ny, nx, power)
         + vortex_weight * _vortex(kv, ny, nx, n_vortices, periodic_x)
         + noise * jax.random.normal(kn, (ny, nx), jnp.float32))
    return _normalize(f)


_GENERATORS = {"grf": _grf, "vortex": _vortex, "multiscale": _multiscale}


def _one(key, ny: int, nx: int, spec: dict) -> jnp.ndarray:
    params = {k: v for k, v in spec.items() if k != "generator"}
    return _GENERATORS[spec["generator"]](key, ny, nx, **params)


@functools.partial(jax.jit, static_argnames=("shape", "spec"))
def _field(key, i, shape: tuple, spec: str) -> jnp.ndarray:
    """Field ``i`` of a call, from its own key; one program per generator
    spec, whatever ``i`` and the number of fields."""
    ny, nx = shape
    return _one(jax.random.fold_in(key, i), ny, nx, json.loads(spec))


def make_fields(seed: int, n: int, shape, mix) -> jnp.ndarray:
    """(n, ny, nx) float32 fields on the default device, from ``seed``.

    ``mix`` is the traffic file's list of generator specs, e.g.
    ``[{"generator": "grf", "power": 3.0}, ...]``."""
    for spec in mix:
        if spec.get("generator") not in _GENERATORS:
            raise ValueError(f"unknown field generator in {spec}")
    key, shape = seed_key(seed), tuple(int(s) for s in shape)
    specs = [json.dumps(spec, sort_keys=True) for spec in mix]
    return jnp.stack([_field(key, jnp.int32(i), shape, specs[i % len(specs)])
                      for i in range(int(n))])
