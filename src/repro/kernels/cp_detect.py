"""Pallas TPU kernel: critical point detection (paper "CD" stage).

Branch-free 4-neighbor stencil classification of a 2-D field, and its
6-neighbor form for a 3-D volume (``cp_detect3``).  Halo handling follows the
shifted-operand pattern (DESIGN.md): XLA materializes the four
edge-replicated shifted views (cheap streaming copies the fusion pass folds
into the kernel's input DMA), the kernel is then purely elementwise over 5
operands and computes edge-validity masks from the grid offsets + iota.
The field extent (ny, nx) is static, so it is baked into the kernel body.

The 3-D kernel runs over a (z, y-tile, x-tile) grid with the z axis
squeezed out of every block: each block is a (ty, tx) tile of one plane,
the z-previous and z-next planes arrive as two more shifted operands, and
the plane index is the grid's first program id.  Each plane is padded in
y and x on its own, so no padding reaches across a plane edge.

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


def _cp3_kernel(f_ref, p_ref, n_ref, t_ref, d_ref, l_ref, r_ref, out_ref, *,
                nz, ny, nx):
    f = f_ref[...]
    kz, ti, tj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    by, bx = f.shape
    kk = kz + jnp.zeros((by, bx), jnp.int32)
    ii = ti * by + jax.lax.broadcasted_iota(jnp.int32, (by, bx), 0)
    jj = tj * bx + jax.lax.broadcasted_iota(jnp.int32, (by, bx), 1)
    # (neighbour, exists) along z, y, x: previous then next
    axes = (((p_ref[...], kk > 0), (n_ref[...], kk < nz - 1)),
            ((t_ref[...], ii > 0), (d_ref[...], ii < ny - 1)),
            ((l_ref[...], jj > 0), (r_ref[...], jj < nx - 1)))

    is_min = is_max = interior = None
    all_hi = all_lo = one_sided = None
    for (a, has_a), (b, has_b) in axes:
        # a missing neighbour counts as both higher and lower (edge rule)
        hi = ((a > f) | ~has_a) & ((b > f) | ~has_b)
        lo = ((a < f) | ~has_a) & ((b < f) | ~has_b)
        both = has_a & has_b
        pair_hi = both & (a > f) & (b > f)
        pair_lo = both & (a < f) & (b < f)
        if is_min is None:
            is_min, is_max, interior = hi, lo, both
            all_hi, all_lo, one_sided = pair_hi, pair_lo, pair_hi | pair_lo
        else:
            is_min, is_max, interior = is_min & hi, is_max & lo, interior & both
            all_hi, all_lo = all_hi & pair_hi, all_lo & pair_lo
            one_sided = one_sided & (pair_hi | pair_lo)
    # saddle: every axis pair strictly on one side, not all on the same one
    is_saddle = interior & one_sided & ~all_hi & ~all_lo

    zero = jnp.zeros((by, bx), jnp.int32)
    lab = jnp.where(is_min, zero + 1, zero)
    lab = jnp.where(is_saddle, zero + 2, lab)
    lab = jnp.where(is_max, zero + 3, lab)
    out_ref[...] = lab


def _shifts3(volume: jnp.ndarray):
    """Edge-replicated z-prev/z-next/t/d/l/r shifted views of a volume."""
    p = jnp.pad(volume, 1, mode="edge")
    c = slice(1, -1)
    return (p[:-2, c, c], p[2:, c, c], p[c, :-2, c], p[c, 2:, c],
            p[c, c, :-2], p[c, c, 2:])


def plane_tiles(shape, ty: int, tx: int):
    """(pad, grid, block spec) of a (nz, ny, nx) volume tiled plane by plane:
    y and x padded to the tile, one grid step per plane and tile."""
    nz, ny, nx = shape
    py, px = (-ny) % ty, (-nx) % tx
    pad = ((0, 0), (0, py), (0, px))
    grid = (nz, (ny + py) // ty, (nx + px) // tx)
    spec = pl.BlockSpec((None, ty, tx), lambda k, i, j: (k, i, j))
    return pad, grid, spec


@functools.partial(jax.jit, static_argnames=("ty", "tx", "interpret"))
def cp_detect3(volume: jnp.ndarray, ty: int = DEFAULT_TY,
               tx: int = DEFAULT_TX, interpret: bool = False) -> jnp.ndarray:
    """Classify every point of a 3-D volume over its 6 axis neighbours ->
    int32 labels (same shape)."""
    nz, ny, nx = volume.shape
    pad, grid, spec = plane_tiles(volume.shape, ty, tx)
    v = volume.astype(jnp.float32)
    ops = [jnp.pad(a, pad, mode="edge") for a in (v, *_shifts3(v))]
    out = pl.pallas_call(
        functools.partial(_cp3_kernel, nz=nz, ny=ny, nx=nx),
        grid=grid,
        in_specs=[spec] * 7,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(ops[0].shape, jnp.int32),
        interpret=interpret,
    )(*ops)
    return out[:, :ny, :nx]
