"""Pallas TPU kernel: tiled bit-packing (SZp "BE" stage, phase 1).

Every block of K magnitudes is packed at its LOCAL offset 0 into
``ceil(K*max_width/8)`` bytes — the global compaction (a collision-free
scatter to the per-block byte offsets) stays in XLA, see
``core.bitpack.compact_local_bytes``.  This removes the two costs of the
legacy one-shot packer: the per-output-byte ``searchsorted`` byte->block
map, and the 32-bit worst-case capacity (the static ``max_width`` comes
from the measured widths lifted to a ``core.bitpack.WIDTH_BUCKETS`` entry).

Kernel form (branch-free VPU ops on a (TB, NBM) tile): for each of the K
values, its w-bit window lands at stream bits [i*w, i*w+w); the
contribution to output byte j is ``v << s`` / ``v >> -s`` with
``s = i*w - 8*j``, masked to the overlap — a K-step unrolled shift-and-or.

Validated against ``core.bitpack.local_pack_bytes`` in interpret mode
(tests/test_bitpack.py, tests/test_backend_parity.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TB = 256  # blocks per grid instance
# The K-step unrolled body keeps ~0.64*K (TB, NBM) int32 temporaries alive
# in VMEM (measured: 64 MB at K=256, TB=256, NBM=384); keep them within a
# budget under the 16 MB scoped-VMEM default.
_VMEM_BUDGET = 12 << 20


def tile_rows(k: int, max_width: int, tb: int = DEFAULT_TB) -> int:
    """Rows per grid instance: ``tb``, cut (in steps of the 32-row uint8
    tile) until the unrolled body's temporaries fit the VMEM budget."""
    nbm = (k * max_width + 7) // 8
    fit = _VMEM_BUDGET // max(1, (64 * k // 100) * nbm * 4)
    return max(32, min(tb, fit // 32 * 32))


def _make_pack_kernel(k: int, nbm: int):
    def kernel(mags_ref, widths_ref, out_ref):
        mags = mags_ref[...].astype(jnp.uint32)           # (TB, K)
        w = widths_ref[...]                               # (TB, 1) i32
        tb = mags.shape[0]
        j8 = 8 * jax.lax.broadcasted_iota(jnp.int32, (tb, nbm), 1)
        acc = jnp.zeros((tb, nbm), jnp.uint32)
        for i in range(k):
            v = mags[:, i:i + 1]                          # (TB, 1)
            s = i * w - j8                                # (TB, NBM)
            sl = jnp.clip(s, 0, 31).astype(jnp.uint32)
            sr = jnp.clip(-s, 0, 31).astype(jnp.uint32)
            contrib = jnp.where(s >= 0, v << sl, v >> sr) & jnp.uint32(0xFF)
            valid = (s < 8) & (s > -w) & (w > 0)
            acc = acc | jnp.where(valid, contrib, jnp.uint32(0))
        out_ref[...] = acc.astype(jnp.uint8)
    return kernel


@functools.partial(jax.jit, static_argnames=("max_width", "tb", "interpret"))
def local_pack_blocks(mags: jnp.ndarray, widths: jnp.ndarray,
                      max_width: int = 32, tb: int = DEFAULT_TB,
                      interpret: bool = False) -> jnp.ndarray:
    """Per-block local pack -> (B, ceil(K*max_width/8)) uint8.

    Block b's first ``ceil(K*widths[b]/8)`` bytes equal its slice of the
    ``core.bitpack.pack_blocks`` stream; the tail is zero.  B must be a
    multiple of ``tb`` (the ops.py wrapper pads).
    """
    b, k = mags.shape
    assert b % tb == 0, f"B={b} not a multiple of tile {tb}"
    nbm = (k * max_width + 7) // 8
    out = pl.pallas_call(
        _make_pack_kernel(k, nbm),
        grid=(b // tb,),
        in_specs=[
            pl.BlockSpec((tb, k), lambda i: (i, 0)),
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, nbm), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nbm), jnp.uint8),
        interpret=interpret,
    )(mags.astype(jnp.uint32), widths.astype(jnp.int32)[:, None])
    return out
