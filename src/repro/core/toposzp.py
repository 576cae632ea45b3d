"""TopoSZp: the full topology-aware compression pipeline (paper Sec. IV).

Compression  :  CD + RP  ->  QZ  ->  B + LZ  ->  BE        (Sec. IV-A)
Decompression:  BE^ -> LZ^+B^ -> QZ^ -> MD^ -> CP^+RP^ -> RS^  (Sec. IV-B)

Stream layout = SZp sections (1)-(5) plus (6) the 2-bit critical-point label
map and (7) the relative-order metadata, itself re-compressed with a second
lossless B+LZ+BE pass (paper Fig. 6).

Every stage dispatches through ``kernels.ops`` (``backend={"pallas",
"interpret","jnp"}``; ``None`` resolves to the hardware default): CD via
``cp_detect``, QZ+LZ via the fused ``szp_quant``, BE via the tiled
two-pass pack (static capacity = measured width bucket, see core/szp.py),
QZ^ via ``szp_dequant`` behind the |code|<2^24 tri-matmul guard, CP^+RP^
via ``extrema_restore`` and RS^ via the separable ``shepard_refine``.
Stream bytes are bit-identical across all backends.  The rank stream
(section 7) must stay lossless, so its decode always takes the exact
int32-cumsum path regardless of backend.

Every entry point takes a 2-D field (ny, nx) or a 3-D volume (nz, ny,
nx); CD, CP^+RP^ and RS^ pick their 4- or 6-neighbour kernels by the
field's rank, the rest of the pipeline works on the flattened field.

``toposzp_compress_batch`` / ``toposzp_decompress_batch`` stack N
same-shape fields into ONE compiled call (compress: vmap = grid over the
batch dim; decompress: a loop over the fields), so multi-field workloads (checkpoint shards, the fig7 bench) stop paying a
dispatch + trace per field.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import bitpack
from repro.core.guarantees import enforce_no_fp_ft
from repro.core.quantize import quantize
from repro.core.rbf import refine_saddles
from repro.core.relative_order import compute_ranks
from repro.core.stencils import apply_extrema_stencils
from repro.core.szp import (DEFAULT_BLOCK, HEADER_BYTES, SZpParts,
                            _assemble_parts, _blocked_codes, _blocked_field,
                            _delta_blocks, _obs_stream, _pack_switch,
                            _quiet_donation, _unpack_sections,
                            decompress_codes, tri_guard_width)
from repro.kernels import ops


class TopoSZpCompressed(NamedTuple):
    """Full TopoSZp stream: SZp sections + topology metadata sections.

    The batched APIs use the same container with a leading batch axis on
    every array (``batch_slice`` recovers the per-field view).
    """
    szp: SZpParts                # sections (1)-(5)
    labels2b: jnp.ndarray        # section (6): packed 2-bit label map
    ranks: SZpParts              # section (7): lossless B+LZ+BE over ranks
    n_cp: jnp.ndarray            # () int32 critical point count
    nbytes: jnp.ndarray          # () int32 total compressed size


def _cp_first_dest(labels_flat: jnp.ndarray) -> jnp.ndarray:
    """Destination index of every point under the stable CP-first partition.

    Equivalent to inverting ``argsort(labels == 0, stable)`` but realized
    as two prefix sums + a select — O(n) instead of a full sort on the
    decompression AND compression hot paths.

    Beyond-paper ratio optimization (§Perf/compression): ranks are stored
    only for the n_cp critical points instead of densely — the decompressor
    recovers positions from the label map, so only ceil(n_cp/block) blocks
    of the rank stream carry data and the accounting/serialization slices
    the stream there.
    """
    noncp = labels_flat == 0
    n_cp = (~noncp).sum()
    c_cp = jnp.cumsum(~noncp) - 1
    c_non = jnp.cumsum(noncp) - 1
    return jnp.where(noncp, n_cp + c_non, c_cp).astype(jnp.int32)


def rank_stream_bytes(n_cp: jnp.ndarray, payload_nbytes: jnp.ndarray,
                      block: int = DEFAULT_BLOCK) -> jnp.ndarray:
    """Size of the sparse rank section: only the used block prefix."""
    ub = (n_cp + block - 1) // block
    return (HEADER_BYTES + (ub + 7) // 8 + ub + (block * ub + 7) // 8
            + 4 * ub + payload_nbytes).astype(jnp.int32)


# --------------------------------------------------------------------------
# Compression
# --------------------------------------------------------------------------

def _compress_measure(field: jnp.ndarray, eb: float, block: int,
                      backend: str):
    """Single-field pass 1: everything except the width-bucketed BE pack."""
    field = field.astype(jnp.float32)
    codes = quantize(field, eb)

    # --- CD + RP (the lightweight topology stage, before lossy QZ) ---
    with jax.named_scope("toposzp.stage_detect"):
        with jax.named_scope("toposzp.stage_cd"):
            labels = ops.cp_detect(field, backend=backend)
        with jax.named_scope("toposzp.stage_rp"):
            ranks = compute_ranks(field, labels, codes)

    # --- QZ + LZ fused over (B, K) blocks ---
    with jax.named_scope("toposzp.stage_quant"):
        first, mags, signs, widths = ops.szp_quant(
            _blocked_field(field, block), eb, backend=backend)

        # --- metadata sections ---
        labels_flat = labels.reshape(-1)
        labels2b = bitpack.pack_2bit(labels_flat)
        n_cp = (labels_flat != 0).sum().astype(jnp.int32)
        dest = _cp_first_dest(labels_flat)
        ranks_sorted = jnp.zeros(labels_flat.shape[0],
                                 jnp.int32).at[dest].set(
            ranks.reshape(-1), unique_indices=True)   # CP ranks first
        rfirst, rmags, rsigns, rwidths = _delta_blocks(
            _blocked_codes(ranks_sorted, block))
    return ((first, mags, signs, widths), (rfirst, rmags, rsigns, rwidths),
            labels2b, n_cp, widths.max(), rwidths.max())


def _compress_measure_batch(fields: jnp.ndarray, eb: float, block: int,
                            backend: str):
    """Batched pass 1; both width maxes are reduced over the WHOLE batch
    in-graph so the caller's bucket decision reads one scalar pair
    instead of N per-field maxes."""
    main, rank, labels2b, n_cp, w_max, rw_max = jax.vmap(
        lambda f: _compress_measure(f, eb, block, backend))(fields)
    return main, rank, labels2b, n_cp, w_max.max(), rw_max.max()


# Pass 1 is one program per (shape, backend), shared by the classic and
# the resident compress (its rank sort dominates the compile time).
_measure_one, _measure_one_donated, _measure_batch, _measure_batch_donated = (
    jax.jit(fn, static_argnames=("block", "backend"), donate_argnums=don)
    for fn in (_compress_measure, _compress_measure_batch)
    for don in ((), (0,)))


@functools.partial(jax.jit, static_argnames=("block", "mw_main", "mw_rank",
                                             "backend", "batched"))
def _pack_streams(main, rank, labels2b, n_cp, block: int, mw_main: int,
                  mw_rank: int, backend: str,
                  batched: bool = False) -> TopoSZpCompressed:
    """Pass 2: tiled BE pack of both streams at static capacity buckets."""
    def pack(args):
        szp_parts = _assemble_parts(*args[0], mw_main, backend=backend)
        rank_parts = _assemble_parts(*args[1], mw_rank, backend=backend)
        return szp_parts, rank_parts
    with jax.named_scope("toposzp.stage_pack"):
        if batched:
            szp_parts, rank_parts = jax.vmap(pack)((main, rank))
            labels_bytes = labels2b.shape[1]
        else:
            szp_parts, rank_parts = pack((main, rank))
            labels_bytes = labels2b.shape[0]
    nbytes = (szp_parts.nbytes + labels_bytes
              + rank_stream_bytes(n_cp, rank_parts.payload_nbytes, block))
    return TopoSZpCompressed(szp_parts, labels2b, rank_parts, n_cp,
                             nbytes.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("block", "backend", "batched"))
def _pack_resident(main, rank, labels2b, n_cp, block: int, backend: str,
                   batched: bool = False) -> TopoSZpCompressed:
    """Pass 2 on device: shared-bucket switch pack, no host syncs.  Main
    and rank streams are packed at the SHARED bucket of their joint max
    width (6 ``lax.switch`` branches instead of 36 bucket pairs; batched,
    the switch sits outside the vmap with one bucket for the batch);
    valid bytes and the serialized stream are identical to the
    per-stream-bucket classic pack."""
    with jax.named_scope("toposzp.stage_pack"):
        szp_parts, rank_parts = _pack_switch((main, rank), block, backend,
                                             batched=batched)
    nbytes = (szp_parts.nbytes + labels2b.shape[-1]
              + rank_stream_bytes(n_cp, rank_parts.payload_nbytes, block))
    return TopoSZpCompressed(szp_parts, labels2b, rank_parts, n_cp,
                             nbytes.astype(jnp.int32))


def _compress_resident(measure, fields, eb, block: int, backend: str,
                       batched: bool) -> TopoSZpCompressed:
    """Device-resident compress: pass 1 + on-device pass 2, no host
    syncs; composes under an enclosing ``jax.jit``."""
    main, rank, labels2b, n_cp, _, _ = measure(fields, eb, block=block,
                                               backend=backend)
    return _pack_resident(main, rank, labels2b, n_cp, block=block,
                          backend=backend, batched=batched)


def _check_rank(shape, batched: bool) -> int:
    """The grid's rank (2 or 3) of a field or a batch of fields; raises
    before anything is traced otherwise."""
    rank = len(shape) - batched
    if rank not in (2, 3):
        want = "(N, ny, nx) or (N, nz, ny, nx)" if batched else \
            "(ny, nx) or (nz, ny, nx)"
        raise ValueError(f"expected {want} fields, got shape {tuple(shape)}")
    return rank


def _count_points(shape) -> None:
    """Counter ``toposzp.compress.points``: points compressed by a call,
    from its static shape (a per-point reading of any other counter)."""
    obs.counter_add("toposzp.compress.points", math.prod(shape))


def toposzp_compress(field: jnp.ndarray, eb,
                     block: int = DEFAULT_BLOCK,
                     backend: Optional[str] = None, resident: bool = False,
                     donate: bool = False) -> TopoSZpCompressed:
    """Compress a 2-D scalar field or a 3-D volume with topology metadata.

    ``resident=True`` runs the whole compress on device (``lax.switch``
    bucket select; composes under an enclosing ``jax.jit``; worst-case
    payload capacity) with streams byte-identical to the classic two-pass
    path; ``donate=True`` (resident only) donates the field's buffer."""
    rank = _check_rank(field.shape, batched=False)
    backend = ops.resolve_backend(backend)
    _count_points(field.shape)
    if resident:
        with obs.span("compress.resident", pipeline="toposzp",
                      backend=backend, rank=rank):
            with _quiet_donation():
                comp = _compress_resident(
                    _measure_one_donated if donate else _measure_one,
                    field, eb, block, backend, batched=False)
        _obs_stream(comp.szp, "toposzp")
        return comp
    with obs.span("compress.quant", pipeline="toposzp", backend=backend,
                  includes="detect+quant", rank=rank):
        main, rstream, labels2b, n_cp, w_max, rw_max = _measure_one(
            field, eb, block=block, backend=backend)
        # one blocking read for both width maxes
        wm, rwm = np.asarray(jnp.stack([w_max, rw_max]))
        mw_main = bitpack.width_bucket(int(wm))
        mw_rank = bitpack.width_bucket(int(rwm))
    with obs.span("compress.pack", pipeline="toposzp",
                  width_bucket=mw_main, rank_bucket=mw_rank, rank=rank):
        comp = _pack_streams(main, rstream, labels2b, n_cp, block=block,
                             mw_main=mw_main, mw_rank=mw_rank,
                             backend=backend)
    _obs_stream(comp.szp, "toposzp")
    obs.counter_add(f"toposzp.compress.bucket_{mw_main}", 1)
    return comp


def toposzp_compress_batch(fields: jnp.ndarray, eb,
                           block: int = DEFAULT_BLOCK,
                           backend: Optional[str] = None,
                           resident: bool = False,
                           donate: bool = False) -> TopoSZpCompressed:
    """Compress N stacked same-shape fields in one compiled call.

    ``fields`` is (N, ny, nx) or (N, nz, ny, nx); any other rank raises
    ``ValueError``.  Every array of the result carries a leading
    batch axis.  Streams are byte-identical to N per-field calls (the
    shared capacity bucket covers the batch max width; valid bytes are
    unaffected).  Use :func:`batch_slice` / :func:`serialize` helpers to
    recover per-field streams.  ``resident=True``/``donate=True`` as in
    :func:`toposzp_compress`; the classic path's width→bucket decision is
    one reduce over the whole batch (a single scalar-pair read, not N
    per-field syncs).
    """
    rank = _check_rank(fields.shape, batched=True)
    backend = ops.resolve_backend(backend)
    _count_points(fields.shape)
    if resident:
        with obs.span("compress.resident", pipeline="toposzp",
                      backend=backend, batch=fields.shape[0], rank=rank):
            with _quiet_donation():
                comp = _compress_resident(
                    _measure_batch_donated if donate else _measure_batch,
                    fields, eb, block, backend, batched=True)
        _obs_stream(comp.szp, "toposzp")
        return comp
    with obs.span("compress.quant", pipeline="toposzp", backend=backend,
                  includes="detect+quant", batch=fields.shape[0], rank=rank):
        main, rstream, labels2b, n_cp, w_max, rw_max = _measure_batch(
            fields, eb, block=block, backend=backend)
        wm, rwm = np.asarray(jnp.stack([w_max, rw_max]))
        mw_main = bitpack.width_bucket(int(wm))
        mw_rank = bitpack.width_bucket(int(rwm))
    with obs.span("compress.pack", pipeline="toposzp",
                  width_bucket=mw_main, rank_bucket=mw_rank, rank=rank):
        comp = _pack_streams(main, rstream, labels2b, n_cp, block=block,
                             mw_main=mw_main, mw_rank=mw_rank,
                             backend=backend, batched=True)
    _obs_stream(comp.szp, "toposzp")
    obs.counter_add(f"toposzp.compress.bucket_{mw_main}", fields.shape[0])
    return comp


def batch_slice(comp: TopoSZpCompressed, i: int) -> TopoSZpCompressed:
    """Per-field view of a batched stream (arrays indexed on the batch
    axis); byte-identical to the per-field API's output."""
    return jax.tree_util.tree_map(lambda a: a[i], comp)


def pages_as_fields(pages: jnp.ndarray) -> jnp.ndarray:
    """KV-page stack (N, S_page, ...feature dims) -> (N, C, S_page) f32
    2-D field views for the batched compress APIs.

    The feature dims fold into the row (y) axis and the page's sequence dim
    becomes the x axis, so the SZp block deltas run along consecutive
    positions of one channel — the temporally smooth direction of KV data —
    and the CP/rank metadata sees each channel's position profile as a
    scanline.  Inverse: :func:`fields_as_pages`.
    """
    if pages.ndim < 3:
        raise ValueError(f"expected (N, S_page, ...) pages, got {pages.shape}")
    n, s = pages.shape[0], pages.shape[1]
    flat = pages.reshape(n, s, -1)
    return jnp.swapaxes(flat, 1, 2).astype(jnp.float32)


def fields_as_pages(fields: jnp.ndarray, page_shape: Sequence[int],
                    dtype=None) -> jnp.ndarray:
    """(N, C, S_page) field views back to (N, *page_shape) pages."""
    n = fields.shape[0]
    pages = jnp.swapaxes(fields, 1, 2).reshape((n,) + tuple(page_shape))
    return pages if dtype is None else pages.astype(dtype)


# --------------------------------------------------------------------------
# Decompression
# --------------------------------------------------------------------------

def _decode_field(comp: TopoSZpCompressed, shape, eb: float, block: int,
                  recon: str, deq_backend: str, backend: str):
    """BE^ -> LZ^+B^ -> QZ^ -> MD^ for one field -> (base, labels, ranks)."""
    n = math.prod(shape)

    with jax.named_scope("toposzp.stage_decode"):
        # --- QZ^ through the kernel dequant (guarded by the caller) ---
        mags, signs, _ = _unpack_sections(comp.szp, block)
        base = ops.szp_dequant(comp.szp.first, mags, signs[:, 1:], eb,
                               backend=deq_backend)
        if recon == "left":
            base = base - eb
        elif recon != "center":
            raise ValueError(f"unknown recon mode: {recon}")
        base = base.reshape(-1)[:n].reshape(shape)

        # --- MD^: metadata extraction ---
        with jax.named_scope("toposzp.stage_decode_md"):
            labels = bitpack.unpack_2bit(comp.labels2b, n).reshape(shape)
            labels_flat = labels.reshape(-1)
            # sparse rank stream: CP-first order; the stream may be trimmed
            # to its used prefix (deserialization), so decode its actual
            # block count.  Rank codes must stay lossless -> always the
            # exact int32 path.
            n_codes = comp.ranks.widths.shape[0] * block
            ranks_sorted = decompress_codes(comp.ranks, min(n_codes, n),
                                            block=block)
            if n_codes < n:
                ranks_sorted = jnp.concatenate(
                    [ranks_sorted, jnp.zeros(n - n_codes, jnp.int32)])
            dest = _cp_first_dest(labels_flat)
            ranks = ranks_sorted[:n][dest].reshape(shape)
    return base, labels, ranks


def _restore_field(base, labels, ranks, eb: float, rbf_mode: str,
                   backend: str):
    """CP^+RP^ -> RS^ -> FP/FT suppression for one decoded field."""
    with jax.named_scope("toposzp.stage_restore"):
        ext, _ = apply_extrema_stencils(base, labels, ranks, eb,
                                        backend=backend)
        ref, _ = refine_saddles(ext, labels, eb, rbf_mode=rbf_mode,
                                backend=backend)
        out, _ = enforce_no_fp_ft(base, ref, labels)
    return out


@functools.partial(jax.jit, static_argnames=("shape", "block", "rbf_mode",
                                             "recon", "backend"))
def _decompress_one(comp, eb, shape, block, rbf_mode, recon, backend):
    """Single-field decompress behind the in-graph 2^24 dequant guard (a
    ``lax.cond`` on the device-computed max width — no host sync)."""
    def run(deq_backend):
        def fn(c):
            base, labels, ranks = _decode_field(c, shape, eb, block, recon,
                                                deq_backend, backend)
            return _restore_field(base, labels, ranks, eb, rbf_mode, backend)
        return fn
    if backend == "jnp":
        return run("jnp")(comp)
    overflow = (comp.szp.widths.astype(jnp.int32).max()
                >= tri_guard_width(block))
    return jax.lax.cond(overflow, run("jnp"), run(backend), comp)


@functools.partial(jax.jit, static_argnames=("shape", "block", "rbf_mode",
                                             "recon", "backend"))
def _decompress_batch(comp, eb, shape, block, rbf_mode, recon, backend):
    """Batched decompress; the dequant guard ``lax.cond`` is hoisted
    OUTSIDE the per-field loop (scalar max over the whole batch's widths).
    Fields decode one after another (``lax.map``), not under ``vmap``: a
    vmapped FP/FT suppression ``while_loop`` carries the batch axis, and
    the TPU lays it out as the minor dimension — a 16x-padded copy of
    every carried field."""
    def run(deq_backend):
        def one(c):
            base, labels, ranks = _decode_field(c, shape, eb, block, recon,
                                                deq_backend, backend)
            return _restore_field(base, labels, ranks, eb, rbf_mode, backend)
        return lambda cb: jax.lax.map(one, cb)
    if backend == "jnp":
        return run("jnp")(comp)
    overflow = (comp.szp.widths.astype(jnp.int32).max()
                >= tri_guard_width(block))
    return jax.lax.cond(overflow, run("jnp"), run(backend), comp)


def toposzp_decompress(comp: TopoSZpCompressed, shape: Sequence[int],
                       eb, block: int = DEFAULT_BLOCK,
                       rbf_mode: str = "shepard", recon: str = "center",
                       backend: Optional[str] = None) -> jnp.ndarray:
    """Decompress a 2-D field or a 3-D volume of ``shape`` with extrema
    restoration + RBF saddle refinement.

    Device-resident: the 2^24 dequant-exactness guard runs as an in-graph
    ``lax.cond``, so the call never syncs to the host and composes under
    an enclosing ``jax.jit``.

    Guarantees on the output (tested in tests/test_toposzp_guarantees.py),
    independent of the backend:
      * |out - orig| <= 2 eb (relaxed-but-strict bound, paper Table I)
      * zero FP, zero FT w.r.t. the original label map
    """
    rank = _check_rank(tuple(shape), batched=False)
    backend = ops.resolve_backend(backend)
    with obs.span("decompress", pipeline="toposzp", backend=backend,
                  rank=rank):
        out = _decompress_one(comp, eb, shape=tuple(shape), block=block,
                              rbf_mode=rbf_mode, recon=recon,
                              backend=backend)
    obs.counter_add("toposzp.decompress.calls", 1)
    return out


def toposzp_decompress_batch(comp: TopoSZpCompressed, shape: Sequence[int],
                             eb, block: int = DEFAULT_BLOCK,
                             rbf_mode: str = "shepard",
                             recon: str = "center",
                             backend: Optional[str] = None) -> jnp.ndarray:
    """Decompress a batched stream -> (N, *shape), ``shape`` (ny, nx) or
    (nz, ny, nx); equal to stacking N per-field :func:`toposzp_decompress`
    calls.  Device-resident (in-graph dequant guard, no host syncs)."""
    rank = _check_rank(tuple(shape), batched=False)
    backend = ops.resolve_backend(backend)
    nb = comp.szp.widths.shape[0]
    with obs.span("decompress", pipeline="toposzp", backend=backend,
                  batch=nb, rank=rank):
        out = _decompress_batch(comp, eb, shape=tuple(shape), block=block,
                                rbf_mode=rbf_mode, recon=recon,
                                backend=backend)
    obs.counter_add("toposzp.decompress.calls", nb)
    return out


def toposzp_roundtrip(field: jnp.ndarray, eb: float,
                      block: int = DEFAULT_BLOCK,
                      rbf_mode: str = "shepard",
                      backend: Optional[str] = None
                      ) -> Tuple[jnp.ndarray, TopoSZpCompressed]:
    comp = toposzp_compress(field, eb, block=block, backend=backend)
    out = toposzp_decompress(comp, tuple(field.shape), eb, block=block,
                             rbf_mode=rbf_mode, backend=backend)
    return out, comp
