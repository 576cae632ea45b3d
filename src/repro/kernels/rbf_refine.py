"""Pallas TPU kernel: separable Gaussian-RBF (Shepard) refinement.

The convex neighborhood estimate of core/rbf.py with a *global* sigma/radius
is separable:  exp(-(dy^2+dx^2)/2s^2) = g(dy) g(dx),  so

  S(p)  = sum_{|dy|<=r} g(dy) R(p + dy e_y) - f(p),   R = row pass,
  W     = (sum g)^2 - 1,
  est   = S / W.

Two elementwise 7-tap passes (row then column), each a single Pallas kernel
over shifted operands — no halo DMA needed.  ``sigma``/``radius`` are
*traced* scalars: the 7 taps are computed as a tiny jnp vector and fed to
the kernel as an SMEM operand (scalar loads), so one compiled call serves every
parameter value and the batched decompressor can vmap per-field params.
This is the TPU hot path; the per-point-adaptive variant stays on the
pure-jnp path (core/rbf.py), see DESIGN.md "hardware adaptation".

A 3-D volume takes a third pass along z, with taps g(dz) g(dy) g(dx) and
W = (sum g)^3 - 1; its passes are tiled plane by plane
(``cp_detect.plane_tiles``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cp_detect import plane_tiles

DEFAULT_TY, DEFAULT_TX = 128, 128
MAX_RADIUS = 3


def _taps(sigma, radius) -> jnp.ndarray:
    """(7,) f32 Gaussian taps for offsets -3..3, zeroed past ``radius``."""
    o = jnp.arange(-MAX_RADIUS, MAX_RADIUS + 1, dtype=jnp.float32)
    sigma = jnp.asarray(sigma, jnp.float32)
    g = jnp.exp(-(o * o) / (2.0 * sigma * sigma))
    return jnp.where(jnp.abs(o) <= jnp.asarray(radius, jnp.float32), g, 0.0)


def _pass_kernel(taps_ref, *refs):
    # taps arrive as a (1, 7) SMEM row: under vmap the batched (N, 1, 7)
    # operand keeps full-extent trailing block dims, as Mosaic requires
    out_ref = refs[-1]
    acc = None
    for k, ref in enumerate(refs[:-1]):
        term = ref[...] * taps_ref[0, k]
        acc = term if acc is None else acc + term
    out_ref[...] = acc


def _axis_shifts(field: jnp.ndarray, axis: int):
    """Edge-replicated shifts of ``field`` by -3..+3 along ``axis``."""
    pad = [(0, 0)] * field.ndim
    pad[axis] = (MAX_RADIUS, MAX_RADIUS)
    p = jnp.pad(field, pad, mode="edge")
    n = field.shape[axis]
    outs = []
    for o in range(2 * MAX_RADIUS + 1):
        sl = [slice(None)] * field.ndim
        sl[axis] = slice(o, o + n)
        outs.append(p[tuple(sl)])
    return outs


def _run_pass(field: jnp.ndarray, taps: jnp.ndarray, axis: int, ty: int,
              tx: int, interpret: bool) -> jnp.ndarray:
    if field.ndim == 3:
        return _run_pass3(field, taps, axis, ty, tx, interpret)
    ny, nx = field.shape
    py, px = (-ny) % ty, (-nx) % tx
    shifts = [jnp.pad(s, ((0, py), (0, px)), mode="edge")
              for s in _axis_shifts(field, axis)]
    gy, gx = shifts[0].shape[0] // ty, shifts[0].shape[1] // tx
    spec = pl.BlockSpec((ty, tx), lambda i, j: (i, j))
    out = pl.pallas_call(
        _pass_kernel,
        grid=(gy, gx),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [spec] * len(shifts),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(shifts[0].shape, jnp.float32),
        interpret=interpret,
    )(taps.reshape(1, -1), *shifts)
    return out[:ny, :nx]


def _run_pass3(volume, taps, axis: int, ty: int, tx: int, interpret: bool):
    _, ny, nx = volume.shape
    pad, grid, spec = plane_tiles(volume.shape, ty, tx)
    shifts = [jnp.pad(s, pad, mode="edge")
              for s in _axis_shifts(volume, axis)]
    out = pl.pallas_call(
        _pass_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [spec] * len(shifts),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(shifts[0].shape, jnp.float32),
        interpret=interpret,
    )(taps.reshape(1, -1), *shifts)
    return out[:, :ny, :nx]


@functools.partial(jax.jit, static_argnames=("ty", "tx", "interpret"))
def shepard_refine_global(field: jnp.ndarray, sigma=0.75, radius=2,
                          ty: int = DEFAULT_TY, tx: int = DEFAULT_TX,
                          interpret: bool = False) -> jnp.ndarray:
    """Separable convex RBF estimate of every point (center excluded), in
    a 2-D field or a 3-D volume: one pass per axis, the last axis first."""
    f = field.astype(jnp.float32)
    g = _taps(sigma, radius)
    acc = f
    for axis in reversed(range(f.ndim)):
        acc = _run_pass(acc, g, axis=axis, ty=ty, tx=tx, interpret=interpret)
    wsum = g.sum()
    w = wsum * wsum if f.ndim == 2 else wsum * wsum * wsum
    denom = jnp.maximum(w - 1.0, 1e-30)  # minus the center (g0=1)
    return (acc - f) / denom
