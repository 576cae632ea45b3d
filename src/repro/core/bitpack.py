"""Fixed-length byte encoding (SZp "BE" stage) — static-shape JAX bit packing.

SZp stores, per block of K values, the per-block bit width w_b needed for the
largest |delta| in the block, then packs the magnitudes of all K deltas at
w_b bits each into a contiguous byte stream.  On CPU SZp emits this stream
serially; here the packing is fully parallel:

  * per-block byte counts  nb_b = ceil(K * w_b / 8)
  * byte offsets by exclusive prefix sum
  * every *output byte* is produced independently by gathering the (<= 8)
    value bits it covers (searchsorted maps byte -> block)

Unpacking reads, for each value, the <= 5 bytes its bit-window spans and
reassembles the magnitude with 32-bit shifts.  Both directions are jit-able
with static capacities; the dynamic quantity is the valid byte count.

This mirrors the on-disk format byte-for-byte (see core/io.py), the buffers
are simply over-allocated to the static worst case.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.utils import exclusive_cumsum

MAX_WIDTH = 32


def block_nbytes(widths: jnp.ndarray, k: int) -> jnp.ndarray:
    """Per-block packed byte count for K values at widths bits each.

    Widths arrive as the serialized uint8 stream as often as not; the
    product ``k * width`` tops out at 31 * 32 and must not wrap in the
    stream dtype, so compute in int32."""
    return (k * widths.astype(jnp.int32) + 7) // 8


def sum_width(width: int, n_summands: int) -> int:
    """Bit width that holds any sum of ``n_summands`` ``width``-bit magnitudes.

    The block-width growth law of the ring all-reduce (dist/ring.py): a
    partial sum over h members needs at most ``ceil(log2(h))`` extra bits
    over the per-member width, capped at the 32-bit packing limit.
    """
    if n_summands <= 1:
        return min(width, MAX_WIDTH)
    return min(MAX_WIDTH, width + (n_summands - 1).bit_length())


# Static capacity buckets for the two-pass tiled pack: the *measured* max
# block width is lifted to the next bucket so the payload capacity (a static
# shape under jit) shrinks from the 32-bit worst case to ~w_max while the
# small bucket set bounds recompilations to |WIDTH_BUCKETS| variants.
WIDTH_BUCKETS = (1, 2, 4, 8, 16, 32)


def width_bucket(w_max: int) -> int:
    """Smallest static capacity bucket holding measured width ``w_max``."""
    if not 0 <= w_max <= MAX_WIDTH:
        raise ValueError(f"measured width {w_max} outside [0, {MAX_WIDTH}]")
    for b in WIDTH_BUCKETS:
        if w_max <= b:
            return b
    return MAX_WIDTH


def pack_blocks(mags: jnp.ndarray, widths: jnp.ndarray,
                max_width: int = MAX_WIDTH
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pack per-block magnitudes at per-block bit widths.

    Args:
      mags:   (B, K) uint32/int32 magnitudes, each < 2**widths[b].
      widths: (B,) int32 in [0, max_width].  Callers must guarantee the
              bound; it sizes the static output buffer.
      max_width: static cap on every entry of ``widths``.  The ring
              all-reduce passes the deterministic per-hop bound here
              (see :func:`sum_width`) so the shipped buffer shrinks with
              the realizable width instead of the 32-bit worst case.

    Returns:
      buf:    (cap,) uint8 packed stream (valid prefix only),
              cap = B*ceil(K*max_width/8)
      offs:   (B,) int32 exclusive byte offsets per block
      total:  () int32 total valid bytes
    """
    mags = mags.astype(jnp.uint32)
    b_blocks, k = mags.shape
    nb = block_nbytes(widths, k)                       # (B,)
    offs = exclusive_cumsum(nb)                        # (B,)
    total = offs[-1] + nb[-1] if b_blocks > 0 else jnp.int32(0)
    cap = b_blocks * ((k * max_width + 7) // 8)

    j = jnp.arange(cap, dtype=jnp.int32)               # output byte index
    blk = jnp.searchsorted(offs, j, side="right") - 1  # block covering byte j
    blk = jnp.clip(blk, 0, b_blocks - 1)
    jb = j - offs[blk]                                 # byte index inside block
    w = widths[blk]                                    # (cap,)

    # bit positions covered by this byte inside the block's bit stream
    t = jb[:, None] * 8 + jnp.arange(8, dtype=jnp.int32)[None, :]   # (cap, 8)
    w_safe = jnp.maximum(w, 1)[:, None]
    i = jnp.minimum(t // w_safe, k - 1)                # value index
    bit_in_val = t % w_safe
    vals = mags[blk[:, None], i]                       # (cap, 8) gather
    bits = (vals >> bit_in_val.astype(jnp.uint32)) & jnp.uint32(1)
    # mask out bits past the block's bit stream or in zero-width blocks
    valid_bit = (t < (k * w)[:, None]) & (w[:, None] > 0)
    bits = jnp.where(valid_bit, bits, jnp.uint32(0))
    byte = (bits << jnp.arange(8, dtype=jnp.uint32)[None, :]).sum(axis=1)
    byte = jnp.where(j < total, byte, jnp.uint32(0))
    return byte.astype(jnp.uint8), offs, total.astype(jnp.int32)


def local_pack_bytes(mags: jnp.ndarray, widths: jnp.ndarray,
                     max_width: int = MAX_WIDTH) -> jnp.ndarray:
    """Phase 1 of the tiled pack: every block packed at LOCAL offset 0.

    Returns (B, ceil(K*max_width/8)) uint8 — block b's first ``nb_b`` bytes
    are exactly its slice of the :func:`pack_blocks` stream; the tail is 0.
    Per-block independent (no global searchsorted), so the work is
    ``B*ceil(K*w/8)`` bytes instead of the 32-bit worst-case capacity.
    Built one bit plane at a time: a (B, NBM, 8) intermediate would be
    laid out with its minor 8 padded to 128 lanes on the TPU (16x).
    This is the jnp oracle for ``kernels/bitpack_pack.py``.
    """
    mags = mags.astype(jnp.uint32)
    b_blocks, k = mags.shape
    nbm = (k * max_width + 7) // 8
    w = widths.astype(jnp.int32)[:, None]                   # (B, 1)
    w_safe = jnp.maximum(w, 1)
    j8 = 8 * jnp.arange(nbm, dtype=jnp.int32)[None, :]      # (1, nbm)
    byte = jnp.zeros((b_blocks, nbm), jnp.uint32)
    for bit in range(8):
        t = j8 + bit                                        # bit in block
        i = jnp.minimum(t // w_safe, k - 1)                 # value index
        vals = jnp.take_along_axis(mags, i, axis=1)
        bits = (vals >> (t % w_safe).astype(jnp.uint32)) & jnp.uint32(1)
        valid = (t < k * w) & (w > 0)
        byte = byte | (jnp.where(valid, bits, jnp.uint32(0)) << bit)
    return byte.astype(jnp.uint8)


def compact_local_bytes(local: jnp.ndarray, widths: jnp.ndarray, k: int
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Phase 2 of the tiled pack: scatter per-block local bytes to their
    global offsets.  Offsets are disjoint, so the scatter is collision-free
    and deterministic; bytes past ``total`` stay 0 (matching
    :func:`pack_blocks`).  Returns the same (buf, offs, total) contract with
    cap = B * local.shape[1]."""
    b_blocks, nbm = local.shape
    nb = block_nbytes(widths, k)                            # (B,)
    offs = exclusive_cumsum(nb)
    total = offs[-1] + nb[-1] if b_blocks > 0 else jnp.int32(0)
    cap = b_blocks * nbm
    jb = jnp.arange(nbm, dtype=jnp.int32)[None, :]          # (1, nbm)
    # invalid slots all map to the dropped index `cap`, so the indices are
    # NOT unique — don't assert unique_indices (UB under duplicates).
    idx = jnp.where(jb < nb[:, None], offs[:, None] + jb, cap)
    buf = jnp.zeros(cap, jnp.uint8).at[idx.reshape(-1)].set(
        local.reshape(-1), mode="drop")
    return buf, offs, total.astype(jnp.int32)


def pack_blocks_tiled(mags: jnp.ndarray, widths: jnp.ndarray,
                      max_width: int = MAX_WIDTH
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Two-phase tiled pack: bit-identical valid prefix to
    :func:`pack_blocks`, same (buf, offs, total) contract, but the capacity
    and the per-byte gather work scale with ``max_width`` (the measured max
    width lifted to a :data:`WIDTH_BUCKETS` entry) instead of 32 bits."""
    return compact_local_bytes(local_pack_bytes(mags, widths, max_width),
                               widths, mags.shape[1])


def unpack_blocks(buf: jnp.ndarray, widths: jnp.ndarray, k: int) -> jnp.ndarray:
    """Inverse of :func:`pack_blocks` -> (B, K) uint32 magnitudes.

    Every value reads the <= 5 bytes its bit window spans with five 1-D
    gathers over the flat (B*K,) value index.  (One (B, K, 5) gather would
    be laid out with its minor 5 padded to a full 128-lane tile on the
    TPU: 25x the bytes.)"""
    b_blocks = widths.shape[0]
    nb = block_nbytes(widths, k)
    offs = exclusive_cumsum(nb)

    w = widths.astype(jnp.int32)[:, None]               # (B, 1)
    s = jnp.arange(k, dtype=jnp.int32)[None, :] * w     # bit start in block
    byte0 = (offs[:, None] + s // 8).reshape(-1)        # absolute first byte
    sh = (s % 8).astype(jnp.uint32)

    cap = buf.shape[0]

    def byte(j):
        idx = jnp.clip(byte0 + j, 0, cap - 1)
        return buf[idx].astype(jnp.uint32).reshape(b_blocks, k)

    lo = byte(0) | (byte(1) << 8) | (byte(2) << 16) | (byte(3) << 24)
    hi = byte(4)
    # value = (lo >> sh) | (hi << (32 - sh)), guarding the sh == 0 case
    # (shifting a uint32 by 32 is undefined in XLA).
    up = jnp.where(sh == 0, jnp.uint32(0), hi << (jnp.uint32(32) - sh))
    val = (lo >> sh) | up
    # mask to w bits; w == 32 keeps everything, w == 0 yields 0.
    wq = w.astype(jnp.uint32)
    mask = jnp.where(
        wq >= 32, jnp.uint32(0xFFFFFFFF),
        (jnp.uint32(1) << jnp.where(wq >= 32, jnp.uint32(0), wq)) - jnp.uint32(1))
    val = val & mask
    return jnp.where(w > 0, val, jnp.uint32(0))


# ---- fixed-width helpers (sign bits, 2-bit label maps) ---------------------
# Built from strided bit planes and 1-D gathers: an (n/8, 8) or (n/4, 4)
# intermediate would be laid out on the TPU with its minor 8 or 4 padded
# to 128 lanes (16-32x the bytes; 18 GB for one 283M-element gradient).

def pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """Pack a flat {0,1} array into uint8 bytes (little-endian bit order)."""
    n = bits.shape[0]
    b = jnp.pad(bits.astype(jnp.uint32), (0, (-n) % 8))
    out = b[0::8]
    for j in range(1, 8):
        out = out | (b[j::8] << j)
    return out.astype(jnp.uint8)


def unpack_bits(buf: jnp.ndarray, n: int) -> jnp.ndarray:
    """Inverse of :func:`pack_bits`; returns (n,) uint8 of {0,1}."""
    i = jnp.arange(n, dtype=jnp.int32)
    byte = buf[i >> 3].astype(jnp.uint32)
    return ((byte >> (i & 7).astype(jnp.uint32)) & 1).astype(jnp.uint8)


def pack_2bit(vals: jnp.ndarray) -> jnp.ndarray:
    """Pack a flat array of 2-bit codes (0..3) into bytes, 4 per byte."""
    n = vals.shape[0]
    v = jnp.pad(vals.astype(jnp.uint32), (0, (-n) % 4))
    out = v[0::4]
    for j in range(1, 4):
        out = out | (v[j::4] << (2 * j))
    return out.astype(jnp.uint8)


def unpack_2bit(buf: jnp.ndarray, n: int) -> jnp.ndarray:
    """Inverse of :func:`pack_2bit`; returns (n,) int32 codes in 0..3."""
    i = jnp.arange(n, dtype=jnp.int32)
    byte = buf[i >> 2].astype(jnp.uint32)
    return ((byte >> (2 * (i & 3)).astype(jnp.uint32)) & 3).astype(jnp.int32)
