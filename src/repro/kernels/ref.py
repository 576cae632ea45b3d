"""Pure-jnp oracles for every Pallas kernel (the allclose references).

Each function mirrors the corresponding kernel's contract exactly; the
kernel tests sweep shapes/dtypes and assert allclose (or exact equality for
integer outputs) against these.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.critical_points import classify as _classify
from repro.core.quantize import dequantize, quantize
from repro.utils import bitwidth, ulp_step


def szp_quant_blocks_ref(xb: jnp.ndarray, eb: float):
    """Oracle for ``ops.szp_quant`` (QZ + kernels.szp_quant.szp_delta_blocks)."""
    q = quantize(xb, eb)
    first = q[:, 0]
    deltas = q[:, 1:] - q[:, :-1]
    signs = (deltas < 0).astype(jnp.int32)
    mags = jnp.abs(deltas).astype(jnp.uint32)
    widths = bitwidth(mags.max(axis=1))
    return first, mags, signs, widths


def szp_dequant_blocks_ref(first, mags, signs, eb: float):
    """Oracle for kernels.szp_quant.szp_dequant_blocks."""
    deltas = jnp.where(signs > 0, -(mags.astype(jnp.int32)),
                       mags.astype(jnp.int32))
    codes = first[:, None] + jnp.concatenate(
        [jnp.zeros((first.shape[0], 1), jnp.int32),
         jnp.cumsum(deltas, axis=1)], axis=1)
    return dequantize(codes, eb)


def local_pack_ref(mags: jnp.ndarray, widths: jnp.ndarray,
                   max_width: int = 32) -> jnp.ndarray:
    """Oracle for kernels.bitpack_pack.local_pack_blocks."""
    from repro.core.bitpack import local_pack_bytes
    return local_pack_bytes(mags, widths, max_width)


def compact_bytes_ref(local: jnp.ndarray, widths: jnp.ndarray, k: int):
    """Oracle for kernels.bitpack_compact.compact_local_blocks (same
    (buf, offs, total) contract as the XLA scatter)."""
    from repro.core.bitpack import compact_local_bytes
    return compact_local_bytes(local, widths, k)


def cp_detect_ref(field: jnp.ndarray) -> jnp.ndarray:
    """Oracle for kernels.cp_detect.cp_detect (== core classify)."""
    return _classify(field)


def extrema_restore_ref(recon, labels, cur_labels, ranks, eb: float):
    """Oracle for kernels.extrema_restore.extrema_restore."""
    from repro.core.critical_points import neighbor_min_max
    recon = recon.astype(jnp.float32)
    nmin, nmax = neighbor_min_max(recon)
    delta = jnp.maximum(ranks, 1)
    tgt_min = ulp_step(nmin, -delta)
    tgt_max = ulp_step(nmax, +delta)
    lost_min = (labels == 1) & (cur_labels != 1)
    lost_max = (labels == 3) & (cur_labels != 3)
    ok_min = lost_min & (tgt_min >= recon - eb) & (tgt_min <= recon + eb)
    ok_max = lost_max & (tgt_max >= recon - eb) & (tgt_max <= recon + eb)
    out = jnp.where(ok_min, tgt_min, recon)
    return jnp.where(ok_max, tgt_max, out)


def shepard_refine_global_ref(field: jnp.ndarray, sigma=0.75,
                              radius=2) -> jnp.ndarray:
    """Oracle for kernels.rbf_refine.shepard_refine_global.

    Full (non-separable) 7x7 window with global sigma/Chebyshev radius
    (traced scalars, like the kernel), center excluded, edge-replicated —
    the direct form of eq. (2).  A 3-D volume takes the full 7x7x7 window.
    """
    from repro.core.rbf import MAX_RADIUS, _offsets, _window_patches
    f = field.astype(jnp.float32)
    sigma = jnp.asarray(sigma, jnp.float32)
    if f.ndim == 3:
        return _shepard3_ref(f, sigma, radius, MAX_RADIUS)
    patches = _window_patches(f, MAX_RADIUS)
    dy, dx = _offsets(MAX_RADIUS)
    dist2 = (dy ** 2 + dx ** 2).astype(jnp.float32)
    w = jnp.exp(-dist2 / (2.0 * sigma * sigma))
    keep = ((jnp.maximum(jnp.abs(dy), jnp.abs(dx))
             <= jnp.asarray(radius, jnp.int32)) & (dist2 > 0))
    w = jnp.where(keep, w, 0.0)
    return (patches * w[None, None, :]).sum(-1) / w.sum()


def _shepard3_ref(f, sigma, radius, r: int) -> jnp.ndarray:
    """Direct (2r+1)^3-window Shepard estimate of a volume, center
    excluded, edge-replicated: a weighted sum over every offset of the
    window (accumulated, so it holds no window-sized stack of copies)."""
    nz, ny, nx = f.shape
    pad = jnp.pad(f, r, mode="edge")
    num = jnp.zeros_like(f)
    wsum = jnp.float32(0)
    for dz in range(-r, r + 1):
        for dy in range(-r, r + 1):
            for dx in range(-r, r + 1):
                d2 = dz * dz + dy * dy + dx * dx
                cheb = max(abs(dz), abs(dy), abs(dx))
                w = jnp.where((cheb <= jnp.asarray(radius, jnp.int32))
                              & (d2 > 0),
                              jnp.exp(-d2 / (2.0 * sigma * sigma)), 0.0)
                num = num + w * pad[r + dz:r + dz + nz, r + dy:r + dy + ny,
                                    r + dx:r + dx + nx]
                wsum = wsum + w
    return num / wsum
