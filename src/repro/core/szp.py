"""SZp compression pipeline in JAX: QZ -> B + LZ (block delta) -> BE.

Stream layout follows the paper's Fig. 6 (sections 1-5; TopoSZp adds 6-7 in
core/toposzp.py):

  (1) constant-block bitmap            ceil(B/8) bytes
  (2) fixed-length block metadata      B bytes (per-block bit width)
  (3) sign bits for all elements       ceil(n_pad/8) bytes
  (4) first-element value per block    4*B bytes (quantized int32 outlier)
  (5) packed magnitude byte stream     variable (sum of per-block widths)

The float pipeline dispatches its QZ+LZ / QZ^ math through ``kernels.ops``
(``backend={"pallas","interpret","jnp"}``; streams are bit-identical across
backends) and runs the BE stage as a TWO-PASS tiled pack: pass 1 measures
the per-block widths, the max width is lifted to a static
``bitpack.WIDTH_BUCKETS`` capacity on the host, and pass 2 packs at that
capacity — ``B*ceil(K*w_bucket/8)`` bytes instead of the 32-bit worst case
(typically 4-8x less buffer and gather work).  ``compress_codes`` /
``decompress_codes`` keep the one-shot jit-able worst-case form for
callers that embed them in a larger jit (core/baselines.py)
and for the lossless integer mode (the TopoSZp rank metadata).
"""
from __future__ import annotations

import contextlib
import functools
import warnings
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import bitpack
from repro.kernels import ops
from repro.utils import bitwidth, cdiv, pad_to_multiple

DEFAULT_BLOCK = 32
HEADER_BYTES = 32  # magic/version/n/shape/block/eb — accounted, materialized in io.py

# f32 integer-exactness limit of the MXU tri-matmul dequant (kernels/
# szp_quant.py): every partial delta sum must stay below 2^24.
TRI_DEQUANT_EXACT = 1 << 24


class SZpParts(NamedTuple):
    """Compressed SZp stream (sections as arrays + dynamic byte count)."""
    const_bits: jnp.ndarray      # packed constant-block bitmap
    widths: jnp.ndarray          # (B,) uint8 per-block bit width
    signs: jnp.ndarray           # packed delta sign bits (n_pad bits)
    first: jnp.ndarray           # (B,) int32 first-element (outlier) codes
    payload: jnp.ndarray         # (cap,) uint8 packed magnitudes
    payload_nbytes: jnp.ndarray  # () int32 valid payload bytes
    nbytes: jnp.ndarray          # () int32 total compressed size (with header)


def _blocked_codes(codes: jnp.ndarray, block: int) -> jnp.ndarray:
    q = pad_to_multiple(codes, block, axis=0, mode="edge")
    return q.reshape(-1, block)


def _blocked_field(x: jnp.ndarray, block: int) -> jnp.ndarray:
    """(B, K) blocked float view; edge padding == padding the codes."""
    f = pad_to_multiple(x.astype(jnp.float32).reshape(-1), block, axis=0,
                        mode="edge")
    return f.reshape(-1, block)


def _delta_blocks(qb: jnp.ndarray):
    """B + LZ over (B, K) int32 codes -> (first, mags, signs, widths)."""
    first = qb[:, 0]
    deltas = qb[:, 1:] - qb[:, :-1]                       # (B, K-1)
    signs = (deltas < 0).astype(jnp.int32)
    mags = jnp.abs(deltas).astype(jnp.uint32)
    widths = bitwidth(mags.max(axis=1))                    # (B,)
    return first, mags, signs, widths


def _assemble_parts(first, mags, signs, widths, max_width: int,
                    backend: Optional[str] = None) -> SZpParts:
    """BE stage + fixed sections -> SZpParts (jit-able at static max_width).

    ``backend=None`` keeps the legacy one-shot worst-case packer (no tile
    kernel, 32-bit capacity); a resolved backend runs the tiled two-phase
    pack at the static ``max_width`` bucket.
    """
    nblocks = first.shape[0]
    if backend is None:
        payload, _, total = bitpack.pack_blocks(mags, widths,
                                                max_width=max_width)
    else:
        local = ops.local_pack(mags, widths, max_width=max_width,
                               backend=backend)
        payload, _, total = ops.compact_bytes(local, widths, mags.shape[1],
                                              backend=backend)
    const_bits = bitpack.pack_bits((widths == 0).astype(jnp.uint8))
    signs_full = jnp.concatenate(
        [jnp.zeros((nblocks, 1), jnp.int32), signs], axis=1)
    signs_packed = bitpack.pack_bits(signs_full.reshape(-1).astype(jnp.uint8))
    nbytes = (HEADER_BYTES + const_bits.shape[0] + nblocks
              + signs_packed.shape[0] + 4 * nblocks + total)
    return SZpParts(const_bits, widths.astype(jnp.uint8), signs_packed,
                    first, payload, total, nbytes.astype(jnp.int32))


def compress_codes(codes: jnp.ndarray, block: int = DEFAULT_BLOCK) -> SZpParts:
    """Lossless stages (1)-(5) over int32 codes (B + LZ + BE).

    One-shot, fully jit-able (worst-case 32-bit payload capacity); the
    float pipeline below uses the two-pass tiled pack instead.
    """
    qb = _blocked_codes(codes.astype(jnp.int32).ravel(), block)
    first, mags, signs, widths = _delta_blocks(qb)
    return _assemble_parts(first, mags, signs, widths, bitpack.MAX_WIDTH)


def decompress_codes(parts: SZpParts, n: int,
                     block: int = DEFAULT_BLOCK) -> jnp.ndarray:
    """Invert :func:`compress_codes` -> (n,) int32 codes (exact int path)."""
    mags, signs, nblocks = _unpack_sections(parts, block)
    deltas = jnp.where(signs[:, 1:] > 0, -(mags.astype(jnp.int32)),
                       mags.astype(jnp.int32))
    q = parts.first[:, None] + jnp.concatenate(
        [jnp.zeros((nblocks, 1), jnp.int32), jnp.cumsum(deltas, axis=1)],
        axis=1)
    return q.reshape(-1)[:n]


def _unpack_sections(parts: SZpParts, block: int):
    """BE^ over sections (2)/(3)/(5) -> (mags (B,K-1), signs (B,K), B)."""
    widths = parts.widths.astype(jnp.int32)
    nblocks = widths.shape[0]
    mags = bitpack.unpack_blocks(parts.payload, widths, block - 1)
    signs = bitpack.unpack_bits(parts.signs, nblocks * block) \
        .reshape(nblocks, block)
    return mags, signs, nblocks


# --------------------------------------------------------------------------
# Float pipeline: backend-threaded two-pass compress / guarded decompress
# --------------------------------------------------------------------------

def _quant_fn(x: jnp.ndarray, eb: float, block: int, backend: str):
    """Pass 1: fused QZ+LZ through kernels.ops + measured max width."""
    with jax.named_scope("szp.stage_quant"):
        xb = _blocked_field(x, block)
        first, mags, signs, widths = ops.szp_quant(xb, eb, backend=backend)
        return first, mags, signs, widths, widths.max()


def _quant_batch_fn(xs: jnp.ndarray, eb: float, block: int, backend: str):
    """Batched pass 1; the width max is reduced over the WHOLE batch
    in-graph, so the caller's bucket decision reads one device scalar
    instead of N per-field maxes."""
    first, mags, signs, widths, w_max = jax.vmap(
        lambda x: _quant_fn(x, eb, block, backend))(xs)
    return first, mags, signs, widths, w_max.max()


# Pass 1 is one program per (shape, backend), shared by the classic and
# the resident compress.
_quant_stage, _quant_stage_donated, _quant_stage_batch, \
    _quant_stage_batch_donated = (
        jax.jit(fn, static_argnames=("block", "backend"), donate_argnums=don)
        for fn in (_quant_fn, _quant_batch_fn) for don in ((), (0,)))


@functools.partial(jax.jit, static_argnames=("max_width", "backend"))
def _pack_stage(first, mags, signs, widths, max_width: int,
                backend: str) -> SZpParts:
    """Pass 2: tiled BE pack at the static capacity bucket."""
    with jax.named_scope("szp.stage_pack"):
        return _assemble_parts(first, mags, signs, widths, max_width,
                               backend=backend)


def _obs_stream(parts: SZpParts, pipeline: str) -> None:
    """``<pipeline>.compress.calls``: fields compressed.  Read from array
    SHAPES (host-known without any device read), so recording it keeps the
    zero-sync guarantee on both the classic and the resident path."""
    obs.counter_add(f"{pipeline}.compress.calls",
                    parts.widths.shape[0] if parts.widths.ndim == 2 else 1)


def _bucket_index(w_max: jnp.ndarray) -> jnp.ndarray:
    """Device-side :func:`bitpack.width_bucket`: index into WIDTH_BUCKETS."""
    edges = jnp.asarray(bitpack.WIDTH_BUCKETS[:-1], jnp.int32)
    return (w_max.astype(jnp.int32) > edges).sum()


def _worst_payload_cap(nblocks: int, block: int) -> int:
    """Static payload capacity shared by every ``lax.switch`` branch."""
    return nblocks * (((block - 1) * bitpack.MAX_WIDTH + 7) // 8)


def _pack_switch(streams, block: int, backend: str,
                 batched: bool = False):
    """On-device bucket select + BE pack of one or more delta streams.

    ``streams`` is a tuple of ``(first, mags, signs, widths)`` tuples; all
    of them are packed at the SHARED bucket of the global max width (one
    ``lax.switch`` branch per static WIDTH_BUCKETS capacity instead of a
    branch per bucket combination).  Every branch zero-pads its payloads to
    the worst-case capacity so the branch avals match; the valid prefix
    and all byte counts are untouched, so serialized streams stay
    bit-identical to the host-bucketed two-pass pack.  Returns a tuple of
    SZpParts, one per stream."""
    bdim = 1 if batched else 0
    caps = [_worst_payload_cap(s[0].shape[bdim], block) for s in streams]

    def branch(mw):
        def pack_one(args, cap):
            if batched:
                parts = jax.vmap(lambda f, m, s, w: _assemble_parts(
                    f, m, s, w, mw, backend=backend))(*args)
                pad = ((0, 0), (0, cap - parts.payload.shape[1]))
            else:
                parts = _assemble_parts(*args, mw, backend=backend)
                pad = (0, cap - parts.payload.shape[0])
            return parts._replace(payload=jnp.pad(parts.payload, pad))

        def fn(streams):
            return tuple(pack_one(s, c) for s, c in zip(streams, caps))
        return fn

    w_max = functools.reduce(jnp.maximum,
                             [s[3].max() for s in streams]).astype(jnp.int32)
    bidx = _bucket_index(w_max)
    return jax.lax.switch(bidx, [branch(m) for m in bitpack.WIDTH_BUCKETS],
                          tuple(streams))


@functools.partial(jax.jit, static_argnames=("block", "backend", "batched"))
def _pack_resident(first, mags, signs, widths, block: int, backend: str,
                   batched: bool = False) -> SZpParts:
    """Pass 2 on device: bucket select + BE pack (batched, the bucket
    switch sits outside the vmap: one shared bucket for the batch, a real
    branch instead of a both-sides ``select``)."""
    with jax.named_scope("szp.stage_pack"):
        (parts,) = _pack_switch(((first, mags, signs, widths),), block,
                                backend, batched=batched)
    return parts


def _compress_resident(quant, x, eb, block: int, backend: str,
                       batched: bool) -> SZpParts:
    """Device-resident compress: pass 1 + on-device pass 2, no host
    syncs; composes under an enclosing ``jax.jit``."""
    first, mags, signs, widths, _ = quant(x, eb, block=block,
                                          backend=backend)
    return _pack_resident(first, mags, signs, widths, block=block,
                          backend=backend, batched=batched)


@contextlib.contextmanager
def _quiet_donation():
    """Donation is best-effort: no compress output matches the input's
    f32 aval, so backends that only reuse donated buffers via exact
    aliasing (CPU) warn and keep the input alive.  The flag still frees
    the buffer where the allocator supports it (TPU)."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def szp_compress(x: jnp.ndarray, eb, block: int = DEFAULT_BLOCK,
                 backend: Optional[str] = None, resident: bool = False,
                 donate: bool = False) -> SZpParts:
    """Full SZp compression of a float field (any shape; flattened
    row-major).  Stream bytes are bit-identical across backends and modes.

    ``resident=False`` (default) keeps the two-pass pack: one host sync
    reads the measured max width and the payload capacity is the measured
    WIDTH_BUCKETS bucket (smallest buffer).  ``resident=True`` runs the
    whole compress as device-only computation (``lax.switch`` over the
    static buckets) and is safe to call inside an enclosing ``jax.jit`` —
    the payload is padded to the worst-case capacity but every byte count
    and the valid prefix are identical.  ``donate=True`` (resident only)
    donates ``x``'s buffer to the computation.
    """
    backend = ops.resolve_backend(backend)
    if resident:
        with obs.span("compress.resident", pipeline="szp", backend=backend):
            with _quiet_donation():
                parts = _compress_resident(
                    _quant_stage_donated if donate else _quant_stage,
                    x, eb, block, backend, batched=False)
        _obs_stream(parts, "szp")
        return parts
    with obs.span("compress.quant", pipeline="szp", backend=backend):
        first, mags, signs, widths, w_max = _quant_stage(
            x, eb, block=block, backend=backend)
        mw = bitpack.width_bucket(int(w_max))   # the existing sync point
    with obs.span("compress.pack", pipeline="szp", width_bucket=mw):
        parts = _pack_stage(first, mags, signs, widths, mw, backend)
    _obs_stream(parts, "szp")
    obs.counter_add(f"szp.compress.bucket_{mw}", 1)
    return parts


@functools.partial(jax.jit,
                   static_argnames=("n", "block", "recon", "backend"))
def _dequant_stage(parts: SZpParts, n: int, eb: float, block: int,
                   recon: str, backend: str) -> jnp.ndarray:
    """BE^ -> LZ^+B^ -> QZ^ through kernels.ops -> (n,) float32."""
    with jax.named_scope("szp.stage_restore"):
        mags, signs, _ = _unpack_sections(parts, block)
        out = ops.szp_dequant(parts.first, mags, signs[:, 1:], eb,
                              backend=backend)
        if recon == "left":
            out = out - eb
        elif recon != "center":
            raise ValueError(f"unknown recon mode: {recon}")
        return out.reshape(-1)[:n]


def tri_guard_width(block: int) -> int:
    """Smallest block width whose deltas can overflow the 2^24 tri-matmul
    exactness limit — the static threshold of the device-side dequant
    guard (``w_max >= tri_guard_width(block)`` <=> the host-side
    :func:`_dequant_backend_for` check)."""
    for w in range(bitpack.MAX_WIDTH + 1):
        if (block - 1) * ((1 << min(w, 31)) - 1) >= TRI_DEQUANT_EXACT:
            return w
    return bitpack.MAX_WIDTH + 1


@functools.partial(jax.jit,
                   static_argnames=("n", "block", "recon", "backend"))
def _dequant_guarded(parts: SZpParts, n: int, eb, block: int,
                     recon: str, backend: str) -> jnp.ndarray:
    """Dequant behind the in-graph 2^24 guard: a ``lax.cond`` on the
    device-computed max width picks the exact int32-cumsum path when the
    tri-matmul could lose integer exactness — no host sync."""
    if backend == "jnp":
        return _dequant_stage(parts, n, eb, block, recon, "jnp")
    overflow = parts.widths.astype(jnp.int32).max() >= tri_guard_width(block)
    return jax.lax.cond(
        overflow,
        lambda p: _dequant_stage(p, n, eb, block, recon, "jnp"),
        lambda p: _dequant_stage(p, n, eb, block, recon, backend),
        parts)


def szp_decompress(parts: SZpParts, shape: Sequence[int], eb,
                   block: int = DEFAULT_BLOCK, recon: str = "center",
                   backend: Optional[str] = None) -> jnp.ndarray:
    """Full SZp decompression back to a float field of ``shape``.

    Device-resident: the 2^24 dequant-exactness guard runs as an in-graph
    ``lax.cond``, so the call never syncs to the host and composes under
    an enclosing ``jax.jit``."""
    backend = ops.resolve_backend(backend)
    n = 1
    for s in shape:
        n *= s
    with obs.span("decompress", pipeline="szp", backend=backend):
        out = _dequant_guarded(parts, n, eb, block, recon, backend)
    obs.counter_add("szp.decompress.calls", 1)
    return out.reshape(shape)


def _dequant_backend_for(parts: SZpParts, block: int, backend: str) -> str:
    """Resolved dequant backend after the 2^24 exactness guard (host-side
    form, one blocking width read; the jit paths use
    :func:`_dequant_guarded` instead)."""
    if backend == "jnp":
        return backend
    w_max = int(np.asarray(parts.widths).max(initial=0))
    max_delta = (1 << min(w_max, 31)) - 1
    if (block - 1) * max_delta >= TRI_DEQUANT_EXACT:
        return "jnp"                    # int32-cumsum fallback (exact)
    return backend


@functools.partial(jax.jit, static_argnames=("max_width", "backend"))
def _pack_stage_batch(first, mags, signs, widths, max_width: int,
                      backend: str) -> SZpParts:
    with jax.named_scope("szp.stage_pack"):
        return jax.vmap(lambda f, m, s, w: _assemble_parts(
            f, m, s, w, max_width, backend=backend))(first, mags, signs,
                                                      widths)


def szp_compress_batch(xs: jnp.ndarray, eb,
                       block: int = DEFAULT_BLOCK,
                       backend: Optional[str] = None, resident: bool = False,
                       donate: bool = False) -> SZpParts:
    """Compress N stacked same-shape fields in one compiled call; every
    array of the result carries a leading batch axis.  Streams are
    byte-identical to N :func:`szp_compress` calls (the shared capacity
    bucket covers the batch max width; valid bytes are unaffected).

    ``resident=True`` keeps the whole batch on device (``lax.switch``
    bucket select, worst-case payload capacity, zero host syncs);
    ``donate=True`` (resident only) donates the stacked input buffer."""
    if xs.ndim < 2:
        raise ValueError(f"expected (N, ...) stacked fields, got {xs.shape}")
    backend = ops.resolve_backend(backend)
    if resident:
        with obs.span("compress.resident", pipeline="szp", backend=backend,
                      batch=xs.shape[0]):
            with _quiet_donation():
                parts = _compress_resident(
                    _quant_stage_batch_donated if donate
                    else _quant_stage_batch,
                    xs, eb, block, backend, batched=True)
        _obs_stream(parts, "szp")
        return parts
    with obs.span("compress.quant", pipeline="szp", backend=backend,
                  batch=xs.shape[0]):
        first, mags, signs, widths, w_max = _quant_stage_batch(
            xs, eb, block=block, backend=backend)
        mw = bitpack.width_bucket(int(w_max))
    with obs.span("compress.pack", pipeline="szp", width_bucket=mw):
        parts = _pack_stage_batch(first, mags, signs, widths, max_width=mw,
                                  backend=backend)
    _obs_stream(parts, "szp")
    obs.counter_add(f"szp.compress.bucket_{mw}", xs.shape[0])
    return parts


@functools.partial(jax.jit,
                   static_argnames=("n", "block", "recon", "backend"))
def _dequant_stage_batch(parts: SZpParts, n: int, eb: float, block: int,
                         recon: str, backend: str) -> jnp.ndarray:
    return jax.vmap(
        lambda p: _dequant_stage(p, n, eb, block, recon, backend))(parts)


@functools.partial(jax.jit,
                   static_argnames=("n", "block", "recon", "backend"))
def _dequant_guarded_batch(parts: SZpParts, n: int, eb, block: int,
                           recon: str, backend: str) -> jnp.ndarray:
    """Batched guarded dequant: the 2^24 ``lax.cond`` is hoisted OUTSIDE
    the vmap (scalar max over the whole batch's widths) — under vmap a
    cond would lower to ``select`` and execute both branches."""
    if backend == "jnp":
        return _dequant_stage_batch(parts, n, eb, block, recon, "jnp")
    overflow = parts.widths.astype(jnp.int32).max() >= tri_guard_width(block)
    return jax.lax.cond(
        overflow,
        lambda p: _dequant_stage_batch(p, n, eb, block, recon, "jnp"),
        lambda p: _dequant_stage_batch(p, n, eb, block, recon, backend),
        parts)


def szp_decompress_batch(parts: SZpParts, shape: Sequence[int], eb,
                         block: int = DEFAULT_BLOCK, recon: str = "center",
                         backend: Optional[str] = None) -> jnp.ndarray:
    """Decompress a batched stream -> (N, *shape); equal to stacking N
    per-field :func:`szp_decompress` calls.  Device-resident (in-graph
    dequant guard, no host syncs)."""
    backend = ops.resolve_backend(backend)
    n = 1
    for s in shape:
        n *= s
    with obs.span("decompress", pipeline="szp", backend=backend,
                  batch=parts.widths.shape[0]):
        out = _dequant_guarded_batch(parts, n=n, eb=eb, block=block,
                                     recon=recon, backend=backend)
    obs.counter_add("szp.decompress.calls", parts.widths.shape[0])
    return out.reshape((parts.widths.shape[0],) + tuple(shape))


def szp_roundtrip(x: jnp.ndarray, eb: float, block: int = DEFAULT_BLOCK,
                  backend: Optional[str] = None
                  ) -> Tuple[jnp.ndarray, SZpParts]:
    parts = szp_compress(x, eb, block=block, backend=backend)
    return szp_decompress(parts, tuple(x.shape), eb, block=block,
                          backend=backend), parts


def compression_ratio(x: jnp.ndarray, parts: SZpParts) -> jnp.ndarray:
    raw = x.size * x.dtype.itemsize
    return raw / parts.nbytes.astype(jnp.float32)


def num_blocks(n: int, block: int = DEFAULT_BLOCK) -> int:
    return cdiv(n, block)
