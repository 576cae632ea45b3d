"""CESM-like fields made on the device from a seed, on the 2-D or 3-D
grid of the call.

A jax.random port of the three generators of ``src/repro/data/fields.py``
(its parameters and, save the ``periodic_x`` option below, its formulas;
device RNG instead of numpy's), so a run's fields cost one small jitted
program per generator and no host work.  Each generator takes the grid's
rank from its shape: the spectrum runs over every axis, a bump has one
Gaussian factor per axis.  On a 2-D grid they trace the same programs as
the 2-D-only generators the 2-D cells were first measured with, so a
seed's 2-D fields keep every bit (``bench/tests/test_bench_fields.py``
holds checksums of them).

* ``grf``: band-limited Gaussian random field (power-law spectrum).  Smooth
  at large scales with critical points spread evenly over the grid: the
  bulk of CESM-ATM's fields.
* ``vortex``: a superposition of Gaussian bumps and dips.  Large smooth,
  flat areas with few, isolated extrema and saddles: the compressible end.
  With ``periodic_x`` the bumps wrap around in x (the last axis), as
  fields on a latitude-longitude grid wrap in longitude.
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


def _along(v: jnp.ndarray, ndim: int, axis: int) -> jnp.ndarray:
    """Vector ``v`` laid along ``axis`` of an ``ndim``-D grid."""
    return v[tuple(slice(None) if a == axis else None for a in range(ndim))]


def _grf(key, shape: tuple, power: float) -> jnp.ndarray:
    white = jax.random.normal(key, shape, jnp.float32)
    last = len(shape) - 1
    freqs = [_along(jnp.fft.rfftfreq(n) if ax == last
                    else jnp.fft.fftfreq(n), len(shape), ax)
             for ax, n in enumerate(shape)]
    k2 = freqs[0] * freqs[0]
    for freq in freqs[1:]:
        k2 = k2 + freq * freq
    k = jnp.sqrt(k2)
    amp = jnp.where(k == 0, 0.0, jnp.maximum(k, 1e-6) ** (-power / 2.0))
    f = jnp.fft.irfftn(jnp.fft.rfftn(white) * amp, s=shape)
    return _normalize(f)


def _vortex(key, shape: tuple, n_vortices: int,
            periodic_x: bool = False) -> jnp.ndarray:
    """Sum of ``a * exp(-r^2 / 2s^2)`` bumps, computed as the separable
    product it is: one Gaussian factor (n_axis, V) per axis, the leading
    ones multiplied out with diag(a) to (points, V), contracted with the
    last axis's (x) factor at full f32 precision.  ``periodic_x``: x runs
    over [0, 1) and distances in x wrap around."""
    kc, ks, ka = jax.random.split(key, 3)
    c = jax.random.uniform(kc, (n_vortices, len(shape)), jnp.float32)
    s = jax.random.uniform(ks, (n_vortices,), jnp.float32, 0.02, 0.12)
    a = jax.random.uniform(ka, (n_vortices,), jnp.float32, -1.0, 1.0)
    *lead_n, nx = shape
    coords = [jnp.linspace(0.0, 1.0, n, dtype=jnp.float32) for n in lead_n]
    if periodic_x:
        x = jnp.arange(nx, dtype=jnp.float32) / nx
    else:
        x = jnp.linspace(0.0, 1.0, nx, dtype=jnp.float32)
    # keep this order (x's distances, the factors, then diag(a)): on a 2-D
    # grid it traces the program the 2-D cells' fields were made by
    dx = x[:, None] - c[None, :, len(lead_n)]
    if periodic_x:
        dx = dx - jnp.round(dx)                      # nearest image
    inv = 1.0 / (2.0 * s * s)
    gs = [jnp.exp(-((y[:, None] - c[None, :, ax]) ** 2) * inv[None, :])
          for ax, y in enumerate(coords)]
    gx = jnp.exp(-(dx ** 2) * inv[None, :])
    lead = gs[0] * a[None, :]
    for g in gs[1:]:
        lead = (lead[:, None, :] * g[None, :, :]).reshape(-1, n_vortices)
    f = jnp.matmul(lead, gx.T, precision=jax.lax.Precision.HIGHEST)
    return _normalize(f.reshape(shape))


def _multiscale(key, shape: tuple, power: float, n_vortices: int,
                grf_weight: float, vortex_weight: float, noise: float,
                periodic_x: bool = False) -> jnp.ndarray:
    kg, kv, kn = jax.random.split(key, 3)
    f = (grf_weight * _grf(kg, shape, power)
         + vortex_weight * _vortex(kv, shape, n_vortices, periodic_x)
         + noise * jax.random.normal(kn, shape, jnp.float32))
    return _normalize(f)


_GENERATORS = {"grf": _grf, "vortex": _vortex, "multiscale": _multiscale}


@functools.partial(jax.jit, static_argnames=("shape", "spec"))
def _field(key, i, shape: tuple, spec: str) -> jnp.ndarray:
    """Field ``i`` of a call, from its own key; one program per generator
    spec, whatever ``i`` and the number of fields."""
    params = json.loads(spec)
    gen = _GENERATORS[params.pop("generator")]
    return gen(jax.random.fold_in(key, i), shape, **params)


def make_fields(seed: int, n: int, shape, mix) -> jnp.ndarray:
    """(n, *shape) float32 fields on the default device, from ``seed``;
    ``shape`` is the configuration's grid, (ny, nx) or (nz, ny, nx).

    ``mix`` is the traffic file's list of generator specs, e.g.
    ``[{"generator": "grf", "power": 3.0}, ...]``."""
    for spec in mix:
        if spec.get("generator") not in _GENERATORS:
            raise ValueError(f"unknown field generator in {spec}")
    key, shape = seed_key(seed), tuple(int(s) for s in shape)
    if len(shape) not in (2, 3):
        raise ValueError(f"grid {shape} is neither 2-D nor 3-D")
    specs = [json.dumps(spec, sort_keys=True) for spec in mix]
    return jnp.stack([_field(key, jnp.int32(i), shape, specs[i % len(specs)])
                      for i in range(int(n))])
