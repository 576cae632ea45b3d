"""Public jit'd wrappers for the Pallas kernels.

Every op takes ``backend=`` with three settings:
  * "pallas"     — pl.pallas_call compiled by Mosaic for the TPU (the
                   production path); asking for it without a TPU raises
  * "interpret"  — same kernel body, interpreted on CPU (validation path)
  * "jnp"        — the pure-jnp oracle from kernels/ref.py

``backend=None`` (every op's default) or "auto" resolves to the
production default for the current hardware ("pallas" on TPU, "jnp"
elsewhere — interpret mode is a validation tool, far too slow to be a CPU
production path) and honors the ``REPRO_KERNEL_BACKEND`` env override
(the CI oracle leg forces "jnp").

Wrappers own all padding/unpadding so callers see natural shapes.  Row
padding follows ONE rule (``_row_tile``): the tile is capped at the padded
row count rounded up to the f32 sublane (8), and rows are padded to a
multiple of the tile — correct for any (b, tb) combination including
b < tb with non-divisible shapes (the old ``min(tb, b)`` adjustment
handed odd, non-sublane-aligned tiles like 100 or 129 to the kernel).
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import bitpack_compact as _bck
from repro.kernels import bitpack_pack as _bpk
from repro.kernels import cp_detect as _cpk
from repro.kernels import extrema_restore as _exk
from repro.kernels import rbf_refine as _rbk
from repro.kernels import szp_quant as _sqk
from repro.kernels import ref as _ref
from repro.core.quantize import quantize
from repro.utils import cdiv, pad_to_multiple

BACKENDS = ("pallas", "interpret", "jnp")
_ENV_BACKEND = "REPRO_KERNEL_BACKEND"


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve a backend knob ('auto'/None -> hardware default).

    An explicit "pallas" without a TPU raises: compiled kernels never
    silently turn into the interpreter."""
    if backend in (None, "auto"):
        backend = os.environ.get(_ENV_BACKEND) or (
            "pallas" if jax.default_backend() == "tpu" else "jnp")
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "pallas" and jax.default_backend() != "tpu":
        raise ValueError(
            f"backend='pallas' needs a TPU; JAX's default backend is "
            f"{jax.default_backend()!r} (use 'interpret' or 'jnp')")
    return backend


def _interp(backend: str) -> bool:
    """interpret= flag for a *resolved* backend."""
    return backend == "interpret"


def _row_tile(b: int, tb: int, align: int = 8) -> int:
    """The shared pad-to-tile rule: tile rows = min(tb, ceil(b/a)*a).

    ``align`` is the sublane tile of the kernel's narrowest operand: 8
    for 32-bit arrays, 32 for the uint8 byte rows of the BE stage."""
    return min(tb, max(align, cdiv(b, align) * align))


def szp_quant(xb: jnp.ndarray, eb: float, backend: Optional[str] = None,
              tb: int = _sqk.DEFAULT_TB):
    """QZ + fused B+LZ over (B, K) blocks -> (first, mags, signs, widths).

    QZ is the oracle's own XLA ``quantize`` on every backend, so the codes
    are bit-identical; the kernel takes over from the int32 codes."""
    backend = resolve_backend(backend)
    if backend == "jnp":
        return _ref.szp_quant_blocks_ref(xb, eb)
    b = xb.shape[0]
    tb = _row_tile(b, tb)
    qp = pad_to_multiple(quantize(xb, eb), tb, axis=0)
    first, mags, signs, widths = _sqk.szp_delta_blocks(
        qp, tb=tb, interpret=_interp(backend))
    return first[:b], mags[:b], signs[:b], widths[:b]


def szp_dequant(first, mags, signs, eb: float,
                backend: Optional[str] = None, tb: int = _sqk.DEFAULT_TB):
    """Inverse of szp_quant -> (B, K) float32 reconstruction.

    The kernel's MXU tri-matmul cumulative sum is exact only while every
    partial delta sum stays below 2^24 (f32 integer exactness); callers
    must guard on the measured widths and fall back to backend="jnp"
    (int32 cumsum) past that — see core.szp._dequant_guarded.
    """
    backend = resolve_backend(backend)
    if backend == "jnp":
        return _ref.szp_dequant_blocks_ref(first, mags, signs, eb)
    b = first.shape[0]
    tb = _row_tile(b, tb)
    fp = pad_to_multiple(first, tb, axis=0)
    mp = pad_to_multiple(mags, tb, axis=0)
    sp = pad_to_multiple(signs.astype(jnp.int32), tb, axis=0)
    out = _sqk.szp_dequant_blocks(fp, mp, sp, eb, tb=tb,
                                  interpret=_interp(backend))
    return out[:b]


def local_pack(mags: jnp.ndarray, widths: jnp.ndarray, max_width: int = 32,
               backend: Optional[str] = None, tb: int = _bpk.DEFAULT_TB):
    """Tiled BE phase 1: per-block local byte pack -> (B, ceil(K*mw/8))."""
    backend = resolve_backend(backend)
    if backend == "jnp":
        return _ref.local_pack_ref(mags, widths, max_width)
    b, k = mags.shape
    tb = _row_tile(b, _bpk.tile_rows(k, max_width, tb), align=32)
    mp = pad_to_multiple(mags, tb, axis=0)
    wp = pad_to_multiple(widths.astype(jnp.int32), tb, axis=0,
                         mode="constant")
    out = _bpk.local_pack_blocks(mp, wp, max_width=max_width, tb=tb,
                                 interpret=_interp(backend))
    return out[:b]


def compact_bytes(local: jnp.ndarray, widths: jnp.ndarray, k: int,
                  backend: Optional[str] = None, tb: int = _bck.DEFAULT_TB):
    """Tiled BE phase 2: per-block rows -> contiguous payload.

    Same ``(buf, offs, total)`` contract as
    ``core.bitpack.compact_local_bytes`` with ``cap = B * local.shape[1]``;
    the offsets prefix sum stays in XLA, only the offset-addressed byte
    moves run in the kernel.
    """
    backend = resolve_backend(backend)
    if backend == "jnp":
        return _ref.compact_bytes_ref(local, widths, k)
    from repro.core.bitpack import block_nbytes
    from repro.utils import exclusive_cumsum
    b = local.shape[0]
    nb = block_nbytes(widths.astype(jnp.int32), k)
    offs = exclusive_cumsum(nb)
    total = (offs[-1] + nb[-1] if b > 0 else jnp.int32(0)).astype(jnp.int32)
    tb = _row_tile(b, tb, align=32)
    lp = pad_to_multiple(local, tb, axis=0, mode="constant")
    nbp = pad_to_multiple(nb, tb, axis=0, mode="constant")
    offp = pad_to_multiple(offs, tb, axis=0, mode="constant")
    buf = _bck.compact_local_blocks(lp, offp, nbp, tb=tb,
                                    interpret=_interp(backend))
    return buf[: b * local.shape[1]], offs, total


def cp_detect(field: jnp.ndarray, backend: Optional[str] = None,
              ty: int = _cpk.DEFAULT_TY, tx: int = _cpk.DEFAULT_TX):
    """Critical point classification of a 2-D field (4 neighbours) or a
    3-D volume (6 neighbours) -> int32 labels."""
    backend = resolve_backend(backend)
    if backend == "jnp":
        return _ref.cp_detect_ref(field)
    detect = _cpk.cp_detect3 if field.ndim == 3 else _cpk.cp_detect
    return detect(field, ty=ty, tx=tx, interpret=_interp(backend))


def extrema_restore(recon, labels, cur_labels, ranks, eb: float,
                    backend: Optional[str] = None,
                    ty: int = _exk.DEFAULT_TY, tx: int = _exk.DEFAULT_TX):
    """Fused lost-extrema restoration of a 2-D field or a 3-D volume ->
    corrected field."""
    backend = resolve_backend(backend)
    if backend == "jnp":
        return _ref.extrema_restore_ref(recon, labels, cur_labels, ranks, eb)
    restore = (_exk.extrema_restore3 if recon.ndim == 3
               else _exk.extrema_restore)
    return restore(recon, labels, cur_labels, ranks, eb,
                                ty=ty, tx=tx, interpret=_interp(backend))


def shepard_refine(field: jnp.ndarray, sigma: float = 0.75, radius: int = 2,
                   backend: Optional[str] = None,
                   ty: int = _rbk.DEFAULT_TY, tx: int = _rbk.DEFAULT_TX):
    """Separable convex RBF estimate (global sigma/radius hot path) of a
    2-D field or a 3-D volume."""
    backend = resolve_backend(backend)
    if backend == "jnp":
        return _ref.shepard_refine_global_ref(field, sigma=sigma, radius=radius)
    return _rbk.shepard_refine_global(field, sigma=sigma, radius=radius,
                                      ty=ty, tx=tx, interpret=_interp(backend))
