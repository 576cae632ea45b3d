"""On-disk serialization of SZp / TopoSZp streams (paper Fig. 6 layout).

The jit-side pipeline keeps the sections as separate fixed-capacity arrays;
this module materializes the actual byte stream (header + sections in Fig. 6
order, payload sliced to its valid length) and parses it back.  Used by the
checkpoint manager and by the true-size accounting in the benchmarks.

The header carries the grid's extent: a 2-D field's stream is version 1
(``ny, nx``), a 3-D volume's version 2 (``nz, ny, nx``, 4 bytes more);
``deserialize_*`` returns the 2- or 3-tuple it read.
"""
from __future__ import annotations

import struct
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.bitpack import width_bucket
from repro.core.szp import DEFAULT_BLOCK, SZpParts
from repro.core.toposzp import TopoSZpCompressed

MAGIC = b"SZPJ"
MAGIC_TOPO = b"TSZP"
# stream version by the grid's rank, and its header layout
STREAM_VERSION = {2: 1, 3: 2}
_HDR = struct.Struct("<4sIIIIdI")   # magic, version, ny, nx, block, eb, nblocks
_HDR3 = struct.Struct("<4sIIIIIdI")  # magic, version, nz, ny, nx, block, ...
_HEADERS = {1: _HDR, 2: _HDR3}


class BadStreamError(ValueError):
    """Raised when a serialized SZp/TopoSZp stream is malformed (bad magic,
    unsupported version, truncated sections).  The checkpoint restore path
    treats it as a corrupt blob and falls back to an older checkpoint."""


def peek_magic(buf: bytes) -> bytes:
    """Magic of a serialized stream without parsing it (b'SZPJ'/b'TSZP')."""
    if len(buf) < 4:
        raise BadStreamError(f"stream too short ({len(buf)} bytes)")
    return bytes(buf[:4])


def _np(a) -> np.ndarray:
    return np.asarray(a)


def serialize_szp(parts: SZpParts, shape: Tuple[int, ...], eb: float,
                  block: int = DEFAULT_BLOCK, magic: bytes = MAGIC) -> bytes:
    shape = tuple(int(s) for s in shape)
    if len(shape) not in STREAM_VERSION:
        raise ValueError(f"a stream holds a 2-D or 3-D grid, got {shape}")
    ver = STREAM_VERSION[len(shape)]
    nblocks = int(_np(parts.widths).shape[0])
    payload = _np(parts.payload)[: int(parts.payload_nbytes)]
    hdr = _HEADERS[ver].pack(magic, ver, *shape, block, float(eb), nblocks)
    return b"".join([
        hdr,
        _np(parts.const_bits).tobytes(),
        _np(parts.widths).tobytes(),
        _np(parts.signs).tobytes(),
        _np(parts.first).astype("<i4").tobytes(),
        payload.tobytes(),
    ])


def deserialize_szp(buf: bytes
                    ) -> Tuple[SZpParts, Tuple[int, ...], float, int]:
    if len(buf) < _HDR.size:
        raise BadStreamError(f"stream too short ({len(buf)} bytes)")
    magic, ver = struct.unpack_from("<4sI", buf, 0)
    if magic not in (MAGIC, MAGIC_TOPO):
        raise BadStreamError(f"bad magic {magic!r}")
    if ver not in _HEADERS:
        raise BadStreamError(f"unsupported stream version {ver}")
    hdr = _HEADERS[ver]
    if len(buf) < hdr.size:
        raise BadStreamError(f"stream too short ({len(buf)} bytes)")
    *shape, block, eb, nblocks = hdr.unpack_from(buf, 0)[2:]
    off = hdr.size
    n_const = -(-nblocks // 8)
    n_sign = -(-(nblocks * block) // 8)
    const_bits = np.frombuffer(buf, np.uint8, n_const, off); off += n_const
    widths = np.frombuffer(buf, np.uint8, nblocks, off); off += nblocks
    signs = np.frombuffer(buf, np.uint8, n_sign, off); off += n_sign
    first = np.frombuffer(buf, "<i4", nblocks, off); off += 4 * nblocks
    payload = np.frombuffer(buf, np.uint8, len(buf) - off, off)
    # capacity = the stream's width BUCKET (not the 32-bit worst case, and
    # not the exact byte count either: capacity is a static shape under
    # jit, so it must be a function of (nblocks, bucket) — a small set —
    # or every distinct payload length would recompile the decompress
    # graph).  Safe because unpack_blocks masks every magnitude to its
    # block width, so clamped reads never leak past-the-end bytes.
    w_max = int(widths.max(initial=0))
    wb = width_bucket(min(w_max, 32))
    cap = max(nblocks * (((block - 1) * wb + 7) // 8), payload.shape[0], 1)
    pay = np.zeros(cap, np.uint8)
    pay[: payload.shape[0]] = payload
    parts = SZpParts(jnp.asarray(const_bits), jnp.asarray(widths),
                     jnp.asarray(signs), jnp.asarray(first.copy()),
                     jnp.asarray(pay), jnp.int32(payload.shape[0]),
                     jnp.int32(len(buf)))
    return parts, tuple(shape), eb, block


def _trim_rank_parts(parts: SZpParts, n_cp: int, block: int) -> SZpParts:
    """Slice the sparse rank stream to its used block prefix (the CP-first
    sort guarantees everything past ceil(n_cp/block) blocks is zero)."""
    ub = max(1, -(-n_cp // block))
    return SZpParts(
        jnp.asarray(_np(parts.const_bits)[: -(-ub // 8)]),
        jnp.asarray(_np(parts.widths)[:ub]),
        jnp.asarray(_np(parts.signs)[: -(-(ub * block) // 8)]),
        jnp.asarray(_np(parts.first)[:ub]),
        parts.payload, parts.payload_nbytes, parts.nbytes)


def serialize_toposzp(comp: TopoSZpCompressed, shape: Tuple[int, ...],
                      eb: float, block: int = DEFAULT_BLOCK) -> bytes:
    base = serialize_szp(comp.szp, shape, eb, block, magic=MAGIC_TOPO)
    labels = _np(comp.labels2b).tobytes()
    n_cp = int(comp.n_cp)
    trimmed = _trim_rank_parts(comp.ranks, n_cp, block)
    ranks = serialize_szp(trimmed, shape, eb, block)
    return b"".join([
        struct.pack("<IIII", len(base), len(labels), len(ranks), n_cp),
        base, labels, ranks,
    ])


def deserialize_toposzp(buf: bytes):
    if len(buf) < 16:
        raise BadStreamError(f"stream too short ({len(buf)} bytes)")
    n_base, n_labels, n_ranks, n_cp = struct.unpack_from("<IIII", buf, 0)
    if 16 + n_base + n_labels + n_ranks > len(buf):
        raise BadStreamError("truncated TopoSZp stream")
    off = 16
    szp_parts, shape, eb, block = deserialize_szp(buf[off:off + n_base])
    off += n_base
    labels2b = jnp.asarray(np.frombuffer(buf, np.uint8, n_labels, off).copy())
    off += n_labels
    rank_parts, _, _, _ = deserialize_szp(buf[off:off + n_ranks])
    comp = TopoSZpCompressed(szp_parts, labels2b, rank_parts,
                             jnp.int32(n_cp), jnp.int32(len(buf)))
    return comp, shape, eb, block
