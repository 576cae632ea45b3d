"""Pallas TPU kernel: byte-stream compaction (SZp "BE" stage, phase 2).

Phase 1 (``kernels/bitpack_pack.py``) leaves every block's packed bytes at
LOCAL offset 0 of a (B, NBM) tile; this kernel moves the first ``nb[b]``
bytes of block b's row to global byte offset ``offs[b]``, producing the
contiguous payload of ``core.bitpack.compact_local_bytes``.

The payload is produced in order, so the kernel streams it.  A VMEM
staging window of two output chunks (``T`` bytes each, ``T`` >= the bytes
of one tile of rows) holds the bytes of the current tile: every row is
lane-rotated to its offset and OR-ed in (the destination ranges are
disjoint, so OR is a store).  When a tile starts past the first chunk,
that chunk is complete: it is DMA'd to its aligned slot of the HBM output
and the window slides by one chunk.  The last step flushes the window and
zero-fills the chunks past it, so bytes past the valid total are 0.  VMEM
use is bounded by the tile, whatever the field size.

Inside the kernel a byte is held in one int32 lane: Mosaic's dynamic lane
rotate and dynamic single-row loads/stores are 32-bit operations.  The
per-row offsets and byte counts are read from SMEM.  The kernel carries a
leading batch axis (grid ``(N, G)``); ``vmap`` of the op maps onto it via
``custom_vmap``, because an HBM output cannot be blocked by the generic
pallas batching rule.

Validated against ``core.bitpack.compact_local_bytes`` in interpret mode
(tests/test_device_resident.py, tests/test_backend_parity.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import cdiv

DEFAULT_TB = 256  # blocks per grid instance
_LANES = 128
_U8_SUBLANES = 32  # uint8 VMEM tile is (32, 128)


def _round_up(x: int, m: int) -> int:
    return cdiv(x, m) * m


def _make_compact_kernel(nbm: int, tb: int, nl: int, r: int, n_chunks: int):
    chunk = r * _LANES                     # bytes per output chunk

    def kernel(local_ref, meta_ref, out_ref, rows, stage, stage8, kbase, sem):
        s, i = pl.program_id(0), pl.program_id(1)

        def flush(k):
            """DMA staging rows [0, r) to output chunk k."""
            stage8[...] = stage[0:r, :].astype(jnp.uint32).astype(jnp.uint8)
            cp = pltpu.make_async_copy(
                stage8, out_ref.at[s, pl.ds(k * r, r), :], sem)
            cp.start()
            cp.wait()

        def slide():
            stage[0:r, :] = stage[r:2 * r, :]
            stage[r:, :] = jnp.zeros((stage.shape[0] - r, _LANES), jnp.int32)

        @pl.when(i == 0)
        def _init():
            stage[...] = jnp.zeros(stage.shape, jnp.int32)
            kbase[0] = 0

        @pl.when(meta_ref[0, 0] - kbase[0] * chunk >= chunk)
        def _advance():
            flush(kbase[0])
            slide()
            kbase[0] += 1

        x = local_ref[...].astype(jnp.int32)             # (tb, nbm)
        for l in range(nl):
            w = min(_LANES, nbm - l * _LANES)
            if w < _LANES:
                rows[l] = jnp.zeros((tb, _LANES), jnp.int32)
                rows[l, :, :w] = x[:, l * _LANES:l * _LANES + w]
            else:
                rows[l] = x[:, l * _LANES:(l + 1) * _LANES]

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        base = kbase[0] * chunk

        def body(j, carry):
            nb = meta_ref[1, j]

            @pl.when(nb > 0)
            def _place():
                p = meta_ref[0, j] - base
                q, c = p // _LANES, p % _LANES
                for l in range(nl):
                    v = rows[l, pl.ds(j, 1), :]
                    v = jnp.where(lane + l * _LANES < nb, v, 0)
                    v = pltpu.roll(v, c, 1)
                    lo = pl.ds(q + l, 1)
                    hi = pl.ds(q + l + 1, 1)
                    stage[lo, :] = stage[lo, :] | jnp.where(lane >= c, v, 0)
                    stage[hi, :] = stage[hi, :] | jnp.where(lane < c, v, 0)
            return carry

        jax.lax.fori_loop(0, tb, body, 0)

        @pl.when(i == pl.num_programs(1) - 1)
        def _finish():
            k = kbase[0]
            flush(k)

            @pl.when(k + 1 < n_chunks)
            def _second():
                slide()
                flush(k + 1)

            stage8[...] = jnp.zeros((r, _LANES), jnp.uint8)

            def zero_fill(kk, carry):
                cp = pltpu.make_async_copy(
                    stage8, out_ref.at[s, pl.ds(kk * r, r), :], sem)
                cp.start()
                cp.wait()
                return carry

            jax.lax.fori_loop(k + 2, n_chunks, zero_fill, 0)

    return kernel


def _compact_call(local, meta, tb: int, interpret: bool):
    """(N, B, NBM) rows + (N, G, 2, tb) offsets/counts -> (N, B*NBM)."""
    n, b, nbm = local.shape
    nl = cdiv(nbm, _LANES)                         # lane rows per block
    r = _round_up(cdiv(tb * nbm, _LANES), _U8_SUBLANES)  # rows per chunk
    n_chunks = cdiv(b * nbm, r * _LANES)
    stage_rows = _round_up(2 * r + nl + 1, 8)
    out = pl.pallas_call(
        _make_compact_kernel(nbm, tb, nl, r, n_chunks),
        grid=(n, b // tb),
        in_specs=[
            pl.BlockSpec((None, tb, nbm), lambda s, i: (s, i, 0)),
            pl.BlockSpec((None, None, 2, tb), lambda s, i: (s, i, 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n, n_chunks * r, _LANES), jnp.uint8),
        scratch_shapes=[
            pltpu.VMEM((nl, tb, _LANES), jnp.int32),
            pltpu.VMEM((stage_rows, _LANES), jnp.int32),
            pltpu.VMEM((r, _LANES), jnp.uint8),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(local, meta)
    return out.reshape(n, -1)[:, :b * nbm]


@functools.lru_cache(maxsize=None)
def _compact_op(tb: int, interpret: bool):
    """The kernel call for any number of leading batch axes, with a vmap
    rule that folds the mapped axis into them."""
    @jax.custom_batching.custom_vmap
    def op(local, meta):
        lead = local.shape[:-2]
        out = _compact_call(local.reshape((-1,) + local.shape[-2:]),
                            meta.reshape((-1,) + meta.shape[-3:]),
                            tb, interpret)
        return out.reshape(lead + out.shape[-1:])

    @op.def_vmap
    def _rule(axis_size, in_batched, local, meta):
        local, meta = [a if batched else
                       jnp.broadcast_to(a, (axis_size,) + a.shape)
                       for a, batched in zip((local, meta), in_batched)]
        return op(local, meta), True

    return op


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def compact_local_blocks(local: jnp.ndarray, offs: jnp.ndarray,
                         nb: jnp.ndarray, tb: int = DEFAULT_TB,
                         interpret: bool = False) -> jnp.ndarray:
    """Move (B, NBM) local rows to their global offsets -> (B*NBM,) uint8.

    ``offs``/``nb`` are (B,) int32 exclusive byte offsets / valid byte
    counts (``core.bitpack.block_nbytes`` of the widths); rows with
    ``nb == 0`` are skipped.  B must be a multiple of ``tb`` (the ops.py
    wrapper pads with ``nb == 0`` rows).  Bytes past the valid total are 0,
    matching the ``compact_local_bytes`` contract.
    """
    b, _ = local.shape
    assert b % tb == 0, f"B={b} not a multiple of tile {tb}"
    meta = jnp.stack([offs.astype(jnp.int32), nb.astype(jnp.int32)])
    meta = meta.reshape(2, b // tb, tb).transpose(1, 0, 2)   # (G, 2, tb)
    return _compact_op(tb, interpret)(local, meta)
