"""Pallas TPU kernels: SZp intra-block delta (B + LZ) and its inverse.

The compression hot loop: for every 1-D block of K quantized codes, emit
the first code (outlier), delta signs, delta magnitudes and the per-block
bit width — everything the BE packer needs — in a single pass.  QZ itself
(``core.quantize.quantize``) runs in XLA just before the kernel (see
``ops.szp_quant``), fused into the blocking copy: it is the same HLO the jnp oracle runs, so the
codes, and hence the streams, are bit-identical across backends whatever
the chip's f32 division lowering.

TPU mapping (DESIGN.md "hardware adaptation"): the (num_blocks, K) layout
puts the SZp block dimension in lanes; a grid instance processes a
(TB, K) tile held in VMEM.  All math is branch-free VPU ops; the bit-width
reduction is a 31-step unrolled compare-accumulate on a signed maximum
(Mosaic reduces signed integers only).  The inverse kernel reconstructs
codes with a cumulative sum expressed as a lower-triangular matmul
(MXU-friendly form of a lane scan) at full f32 precision; the scalar
``eb`` rides in SMEM.

Validated against kernels/ref.py in interpret mode (tests/test_kernels.py);
on real TPUs the same code path runs compiled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TB = 256  # blocks per grid instance


def _delta_kernel(q_ref, first_ref, mags_ref, signs_ref, widths_ref):
    q = q_ref[...]                                    # (TB, K) i32 codes
    first_ref[...] = q[:, :1]
    deltas = q[:, 1:] - q[:, :-1]                     # (TB, K-1)
    neg = deltas < 0
    mags = jnp.where(neg, -deltas, deltas).astype(jnp.uint32)
    mags_ref[...] = mags
    signs_ref[...] = neg.astype(jnp.int32)
    # per-block bit width over the signed view of the magnitudes: a
    # magnitude >= 2^31 reads negative and needs all 32 bits; otherwise
    # the width is the compare ladder on the (non-negative) signed max.
    smags = jax.lax.bitcast_convert_type(mags, jnp.int32)
    mmax = jnp.max(smags, axis=1, keepdims=True)      # (TB, 1)
    mmin = jnp.min(smags, axis=1, keepdims=True)
    w = jnp.zeros_like(mmax)
    for k in range(31):
        w += (mmax >= (1 << k)).astype(jnp.int32)
    widths_ref[...] = jnp.where(mmin < 0, 32, w)


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def szp_delta_blocks(qb: jnp.ndarray, tb: int = DEFAULT_TB,
                     interpret: bool = False):
    """Fused B+LZ over (B, K) blocked int32 codes.

    Returns (first (B,) i32, mags (B, K-1) u32, signs (B, K-1) i32,
    widths (B,) i32).  B must be a multiple of ``tb`` (wrapper pads).
    """
    b, k = qb.shape
    assert b % tb == 0, f"B={b} not a multiple of tile {tb}"
    grid = (b // tb,)
    first, mags, signs, widths = pl.pallas_call(
        _delta_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, k), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
            pl.BlockSpec((tb, k - 1), lambda i: (i, 0)),
            pl.BlockSpec((tb, k - 1), lambda i: (i, 0)),
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, k - 1), jnp.uint32),
            jax.ShapeDtypeStruct((b, k - 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        interpret=interpret,
    )(qb.astype(jnp.int32))
    return first[:, 0], mags, signs, widths[:, 0]


def _dequant_kernel(first_ref, mags_ref, signs_ref, eb_ref, tri_ref, out_ref):
    first = first_ref[...]                            # (TB, 1) i32
    mags = mags_ref[...].astype(jnp.int32)            # (TB, K-1)
    neg = signs_ref[...] > 0
    deltas = jnp.where(neg, -mags, mags)
    # cumulative sum along lanes as a lower-triangular matmul (MXU form);
    # exact for |codes| < 2^24 which the f32 path guarantees here, and the
    # int32 fallback in ops.py covers the full range.
    tri = tri_ref[...]                                # (K-1, K-1) f32 lower-tri
    cs = jax.lax.dot_general(deltas.astype(jnp.float32), tri,
                             (((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    codes = first + jnp.concatenate(
        [jnp.zeros_like(first), cs.astype(jnp.int32)], axis=1)
    eb = eb_ref[0]
    out_ref[...] = codes.astype(jnp.float32) * (2.0 * eb)


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def szp_dequant_blocks(first: jnp.ndarray, mags: jnp.ndarray,
                       signs: jnp.ndarray, eb: float, tb: int = DEFAULT_TB,
                       interpret: bool = False) -> jnp.ndarray:
    """Inverse of QZ + :func:`szp_delta_blocks` -> (B, K) f32."""
    b, km1 = mags.shape
    k = km1 + 1
    assert b % tb == 0
    tri = jnp.asarray(np.tril(np.ones((km1, km1), np.float32)).T)
    ebv = jnp.full((1,), eb, jnp.float32)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(b // tb,),
        in_specs=[
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
            pl.BlockSpec((tb, km1), lambda i: (i, 0)),
            pl.BlockSpec((tb, km1), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((km1, km1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k), jnp.float32),
        interpret=interpret,
    )(first[:, None], mags, signs, ebv, tri)
    return out
