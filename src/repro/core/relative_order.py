"""Relative positioning metadata (paper Sec. IV-A, "RP" stage).

For critical points that fall into the *same* quantization bin, an integer
rank encodes their original value ordering so the decompressor can separate
them again (paper Fig. 5).  Ranks are stored densely (0 at regular points)
and re-compressed losslessly with a second B+LZ+BE pass (paper Sec. IV-A:
"we apply the B+LZ and BE stages a second time ... we omit QZ for this
metadata since it ... must remain lossless").

Direction convention (DESIGN.md clarification): maxima and saddles are
ranked *ascending* by value (rank 1 = smallest), minima *descending*
(rank 1 = largest), so that the +-delta-ULP stencils in core/stencils.py
restore the original order for both extrema kinds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.critical_points import MAXIMA, MINIMA, SADDLE


def _sort_key(x: jnp.ndarray) -> jnp.ndarray:
    """int32 key whose order is ``lax.sort``'s float order: zeros and NaNs
    canonicalized (-0 == +0), then IEEE total order on the bits."""
    x = jnp.where(x == 0, jnp.float32(0), x)
    x = jnp.where(jnp.isnan(x), jnp.float32(jnp.nan), x)
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


# A point's label rides above its flat index in one int32 sort payload.
_TAG_SHIFT = 29
_POS_MASK = (1 << _TAG_SHIFT) - 1


def compute_ranks(field: jnp.ndarray, labels: jnp.ndarray,
                  codes: jnp.ndarray) -> jnp.ndarray:
    """Per-point rank among same-(bin, type) critical points.

    Args:
      field:  float32 original values, of any shape ((ny, nx) or a
              (nz, ny, nx) volume).
      labels: int32 CD labels, same shape.
      codes:  int32 quantization bin indices, same shape.

    Returns:
      int32 ranks of the field's shape; 0 at regular points, >= 1 at
      critical points.

    Points are ordered by (bin, secondary value, index) with two stable
    single-key int32 sorts (least significant key first) — on the TPU a
    sort's compile time grows steeply with its key count, and these two
    compile in a fraction of the time of one three-key lexsort.  Within a
    bin, the points of one type keep their (value, index) order whatever
    the other types interleave, so a point's rank is the running count of
    its own type since the bin's first point: the same ranks as a
    (bin, type, value) lexsort.

    There is no gather and no scatter: every permutation is applied by
    carrying the data through ``lax.sort`` as payload operands.  On a TPU
    v5e, at CESM-ATM 1800x3600 (6.48M points, 16 fields per batch), one
    sort took ~24 ms per field while applying its permutation by a gather
    took 96-150 ms; the gather form applied permutations five times and
    scattered the ranks back, ~670 ms of RP per field (PERF.md).

    The label rides in the index payload as ``index | label << 29``, so a
    field must hold fewer than 2**29 points (checked from the shape).  The
    running count restarts per bin without an indexed lookup: a type's
    inclusive count never decreases along the sorted order, so a running
    ``cummax`` of its exclusive count taken at bin starts (0 elsewhere) is
    the count before the current bin.  A last sort by index returns the
    ranks to the field's order.
    """
    n = field.size
    if n > _POS_MASK:
        raise ValueError(f"compute_ranks takes fewer than 2**{_TAG_SHIFT} "
                         f"points, got {n}")
    f = field.astype(jnp.float32)
    # secondary key: value ascending, except minima descending.  Key and
    # tag are made at the field's shape and flattened after: a sort whose
    # operand fused the minima flip with the flattening took the TPU
    # compiler ~5x as long.
    key = _sort_key(jnp.where(labels == MINIMA, -f, f)).reshape(-1)
    tag = (jnp.arange(n, dtype=jnp.int32).reshape(field.shape)
           | (labels << _TAG_SHIFT)).reshape(-1)
    q = codes.reshape(-1)
    _, q, tag = jax.lax.sort((key, q, tag), num_keys=1, is_stable=True)
    q, tag = jax.lax.sort((q, tag), num_keys=1, is_stable=True)
    lab = tag >> _TAG_SHIFT
    new_bin = jnp.concatenate([jnp.array([True]), q[1:] != q[:-1]])
    rank = jnp.zeros(n, jnp.int32)
    for t in (MINIMA, SADDLE, MAXIMA):
        own = (lab == t).astype(jnp.int32)
        count = jnp.cumsum(own)     # inclusive running count of type t
        before = jax.lax.cummax(jnp.where(new_bin, count - own, 0))
        rank = jnp.where(own == 1, count - before, rank)
    ranks = jax.lax.sort((tag & _POS_MASK, rank), num_keys=1)[1]
    return ranks.reshape(field.shape)
