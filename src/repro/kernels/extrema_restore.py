"""Pallas TPU kernel: fused extrema-stencil restoration (paper CP^+RP^).

For every lost extremum, move the reconstruction delta ULPs past the
min/max of its 4-neighborhood, skipping corrections that leave the +-eb
budget.  ULP stepping is done in the monotone IEEE-754 integer ordering —
pure int32 bit ops on the VPU (see utils.ulp_step for the host version).

Same shifted-operand halo pattern as cp_detect.py; fully elementwise.
The field extent is static (baked into the body); ``eb`` rides in SMEM.
A 3-D volume takes the 6-neighborhood (``extrema_restore3``), tiled plane
by plane as ``cp_detect.cp_detect3`` is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cp_detect import _shifts, _shifts3, plane_tiles

DEFAULT_TY, DEFAULT_TX = 128, 128
_INT32_MIN = -(2 ** 31)


def _f2i(x):
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(i < 0, jnp.int32(_INT32_MIN) - i, i)


def _i2f(i):
    raw = jnp.where(i < 0, jnp.int32(_INT32_MIN) - i, i)
    return jax.lax.bitcast_convert_type(raw, jnp.float32)


def _restore_kernel(eb_ref, f_ref, t_ref, d_ref, l_ref, r_ref,
                    lab_ref, cur_ref, rank_ref, out_ref, *, ny, nx):
    f = f_ref[...]
    t, d, l, r = t_ref[...], d_ref[...], l_ref[...], r_ref[...]
    lab = lab_ref[...]
    cur = cur_ref[...]
    rank = rank_ref[...]
    eb = eb_ref[0]

    ti, tj = pl.program_id(0), pl.program_id(1)
    by, bx = f.shape
    ii = ti * by + jax.lax.broadcasted_iota(jnp.int32, (by, bx), 0)
    jj = tj * bx + jax.lax.broadcasted_iota(jnp.int32, (by, bx), 1)
    has_t, has_d = ii > 0, ii < ny - 1
    has_l, has_r = jj > 0, jj < nx - 1

    big = jnp.float32(3.4e38)
    nmin = jnp.minimum(jnp.minimum(jnp.where(has_t, t, big),
                                   jnp.where(has_d, d, big)),
                       jnp.minimum(jnp.where(has_l, l, big),
                                   jnp.where(has_r, r, big)))
    nmax = jnp.maximum(jnp.maximum(jnp.where(has_t, t, -big),
                                   jnp.where(has_d, d, -big)),
                       jnp.maximum(jnp.where(has_l, l, -big),
                                   jnp.where(has_r, r, -big)))

    out_ref[...] = _restored(f, nmin, nmax, lab, cur, rank, eb)


def _restored(f, nmin, nmax, lab, cur, rank, eb):
    """Lost extrema moved ``rank`` ULPs past their neighbours' min / max,
    where that stays within +-eb."""
    delta = jnp.maximum(rank, 1)
    tgt_min = _i2f(_f2i(nmin) - delta)
    tgt_max = _i2f(_f2i(nmax) + delta)

    lost_min = (lab == 1) & (cur != 1)
    lost_max = (lab == 3) & (cur != 3)
    ok_min = lost_min & (tgt_min >= f - eb) & (tgt_min <= f + eb)
    ok_max = lost_max & (tgt_max >= f - eb) & (tgt_max <= f + eb)

    out = jnp.where(ok_min, tgt_min, f)
    return jnp.where(ok_max, tgt_max, out)


def _restore3_kernel(eb_ref, f_ref, p_ref, n_ref, t_ref, d_ref, l_ref, r_ref,
                     lab_ref, cur_ref, rank_ref, out_ref, *, nz, ny, nx):
    f = f_ref[...]
    kz, ti, tj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    by, bx = f.shape
    kk = kz + jnp.zeros((by, bx), jnp.int32)
    ii = ti * by + jax.lax.broadcasted_iota(jnp.int32, (by, bx), 0)
    jj = tj * bx + jax.lax.broadcasted_iota(jnp.int32, (by, bx), 1)
    nbrs = ((p_ref[...], kk > 0), (n_ref[...], kk < nz - 1),
            (t_ref[...], ii > 0), (d_ref[...], ii < ny - 1),
            (l_ref[...], jj > 0), (r_ref[...], jj < nx - 1))
    big = jnp.float32(3.4e38)
    nmin = jnp.full((by, bx), big)
    nmax = jnp.full((by, bx), -big)
    for v, has in nbrs:
        nmin = jnp.minimum(nmin, jnp.where(has, v, big))
        nmax = jnp.maximum(nmax, jnp.where(has, v, -big))
    out_ref[...] = _restored(f, nmin, nmax, lab_ref[...], cur_ref[...],
                             rank_ref[...], eb_ref[0])


@functools.partial(jax.jit, static_argnames=("ty", "tx", "interpret"))
def extrema_restore(recon: jnp.ndarray, labels: jnp.ndarray,
                    cur_labels: jnp.ndarray, ranks: jnp.ndarray, eb: float,
                    ty: int = DEFAULT_TY, tx: int = DEFAULT_TX,
                    interpret: bool = False) -> jnp.ndarray:
    """Fused lost-extrema restoration; returns the corrected field."""
    ny, nx = recon.shape
    py, px = (-ny) % ty, (-nx) % tx

    def padded(a, mode="edge"):
        return jnp.pad(a, ((0, py), (0, px)), mode=mode)

    f = padded(recon.astype(jnp.float32))
    t, d, l, r = [padded(s) for s in _shifts(recon.astype(jnp.float32))]
    lab = padded(labels, mode="constant")
    cur = padded(cur_labels, mode="constant")
    rank = padded(ranks, mode="constant")
    gy, gx = f.shape[0] // ty, f.shape[1] // tx
    ebv = jnp.full((1,), eb, jnp.float32)
    spec = pl.BlockSpec((ty, tx), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_restore_kernel, ny=ny, nx=nx),
        grid=(gy, gx),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [spec] * 8,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(f.shape, jnp.float32),
        interpret=interpret,
    )(ebv, f, t, d, l, r, lab, cur, rank)
    return out[:ny, :nx]


@functools.partial(jax.jit, static_argnames=("ty", "tx", "interpret"))
def extrema_restore3(recon: jnp.ndarray, labels: jnp.ndarray,
                     cur_labels: jnp.ndarray, ranks: jnp.ndarray, eb: float,
                     ty: int = DEFAULT_TY, tx: int = DEFAULT_TX,
                     interpret: bool = False) -> jnp.ndarray:
    """6-neighbour lost-extrema restoration of a 3-D volume."""
    nz, ny, nx = recon.shape
    pad, grid, spec = plane_tiles(recon.shape, ty, tx)
    v = recon.astype(jnp.float32)
    ops = [jnp.pad(a, pad, mode="edge") for a in (v, *_shifts3(v))]
    ops += [jnp.pad(a, pad) for a in (labels, cur_labels, ranks)]
    ebv = jnp.full((1,), eb, jnp.float32)
    out = pl.pallas_call(
        functools.partial(_restore3_kernel, nz=nz, ny=ny, nx=nx),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [spec] * 10,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(ops[0].shape, jnp.float32),
        interpret=interpret,
    )(ebv, *ops)
    return out[:, :ny, :nx]
