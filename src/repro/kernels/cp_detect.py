"""Pallas TPU kernel: critical point detection (paper "CD" stage).

Branch-free 4-neighbor stencil classification.  Halo handling follows the
shifted-operand pattern (DESIGN.md): XLA materializes the four
edge-replicated shifted views (cheap streaming copies the fusion pass folds
into the kernel's input DMA), the kernel is then purely elementwise over 5
operands and computes edge-validity masks from the grid offsets + iota.
The field extent (ny, nx) is static, so it is baked into the kernel body.

Output labels: REGULAR=0, MINIMA=1, SADDLE=2, MAXIMA=3 (2-bit codes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TY, DEFAULT_TX = 128, 128


def _cp_kernel(f_ref, t_ref, d_ref, l_ref, r_ref, out_ref, *, ny, nx):
    f = f_ref[...]
    t, d, l, r = t_ref[...], d_ref[...], l_ref[...], r_ref[...]

    ti, tj = pl.program_id(0), pl.program_id(1)
    by, bx = f.shape
    ii = ti * by + jax.lax.broadcasted_iota(jnp.int32, (by, bx), 0)
    jj = tj * bx + jax.lax.broadcasted_iota(jnp.int32, (by, bx), 1)
    has_t = ii > 0
    has_d = ii < ny - 1
    has_l = jj > 0
    has_r = jj < nx - 1

    # a missing neighbour counts as both higher and lower (edge rule)
    hi_t, lo_t = (t > f) | ~has_t, (t < f) | ~has_t
    hi_d, lo_d = (d > f) | ~has_d, (d < f) | ~has_d
    hi_l, lo_l = (l > f) | ~has_l, (l < f) | ~has_l
    hi_r, lo_r = (r > f) | ~has_r, (r < f) | ~has_r

    is_min = hi_t & hi_d & hi_l & hi_r
    is_max = lo_t & lo_d & lo_l & lo_r
    interior = has_t & has_d & has_l & has_r
    is_saddle = interior & (((t > f) & (d > f) & (l < f) & (r < f)) |
                            ((t < f) & (d < f) & (l > f) & (r > f)))

    zero = jnp.zeros((by, bx), jnp.int32)
    lab = jnp.where(is_min, zero + 1, zero)
    lab = jnp.where(is_saddle, zero + 2, lab)
    lab = jnp.where(is_max, zero + 3, lab)
    out_ref[...] = lab


def _shifts(field: jnp.ndarray):
    """Edge-replicated t/d/l/r shifted views (host-side XLA slices)."""
    p = jnp.pad(field, 1, mode="edge")
    return (p[:-2, 1:-1], p[2:, 1:-1], p[1:-1, :-2], p[1:-1, 2:])


@functools.partial(jax.jit, static_argnames=("ty", "tx", "interpret"))
def cp_detect(field: jnp.ndarray, ty: int = DEFAULT_TY, tx: int = DEFAULT_TX,
              interpret: bool = False) -> jnp.ndarray:
    """Classify every point of a 2-D field -> int32 labels (same shape)."""
    ny, nx = field.shape
    py, px = (-ny) % ty, (-nx) % tx
    f = jnp.pad(field.astype(jnp.float32), ((0, py), (0, px)), mode="edge")
    t, d, l, r = [jnp.pad(s, ((0, py), (0, px)), mode="edge")
                  for s in _shifts(field.astype(jnp.float32))]
    gy, gx = f.shape[0] // ty, f.shape[1] // tx
    spec = pl.BlockSpec((ty, tx), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_cp_kernel, ny=ny, nx=nx),
        grid=(gy, gx),
        in_specs=[spec] * 5,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(f.shape, jnp.int32),
        interpret=interpret,
    )(f, t, d, l, r)
    return out[:ny, :nx]
