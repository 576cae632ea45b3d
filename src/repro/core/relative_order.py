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

from repro.core.critical_points import MINIMA, REGULAR


def _sort_key(x: jnp.ndarray) -> jnp.ndarray:
    """int32 key whose order is ``lax.sort``'s float order: zeros and NaNs
    canonicalized (-0 == +0), then IEEE total order on the bits."""
    x = jnp.where(x == 0, jnp.float32(0), x)
    x = jnp.where(jnp.isnan(x), jnp.float32(jnp.nan), x)
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


def _stable_order(key: jnp.ndarray, perm: jnp.ndarray) -> jnp.ndarray:
    """``perm`` stably reordered by ``key[perm]`` (one int32 key sort)."""
    return jax.lax.sort((key[perm], perm), num_keys=1, is_stable=True)[1]


def compute_ranks(field: jnp.ndarray, labels: jnp.ndarray,
                  codes: jnp.ndarray) -> jnp.ndarray:
    """Per-point rank among same-(bin, type) critical points.

    Args:
      field:  (ny, nx) float32 original values.
      labels: (ny, nx) int32 CD labels.
      codes:  (ny, nx) int32 quantization bin indices.

    Returns:
      (ny, nx) int32 ranks; 0 at regular points, >= 1 at critical points.

    Points are ordered by (bin, secondary value, index) with two stable
    single-key int32 sorts (least significant key first) — on the TPU a
    sort's compile time grows steeply with its key count, and these two
    compile in a fraction of the time of one three-key lexsort.  Within a
    bin, the points of one type keep their (value, index) order whatever
    the other types interleave, so a point's rank is the running count of
    its own type since the bin's first point: the same ranks as a
    (bin, type, value) lexsort.
    """
    f = field.astype(jnp.float32).reshape(-1)
    lab = labels.reshape(-1)
    q = codes.reshape(-1)
    n = f.shape[0]

    # secondary key: value ascending, except minima descending.
    sec = jnp.where(lab == MINIMA, -f, f)
    pos = jnp.arange(n, dtype=jnp.int32)
    order = _stable_order(q, _stable_order(_sort_key(sec), pos))
    q_s, lab_s = q[order], lab[order]
    new_seg = jnp.concatenate([jnp.array([True]), q_s[1:] != q_s[:-1]])
    seg_start = jax.lax.cummax(jnp.where(new_seg, pos, 0))
    # inclusive running count of each point's own type, restarted per bin
    onehot = (lab_s[:, None] == jnp.arange(1, 4, dtype=jnp.int32)[None, :])
    counts = jnp.cumsum(onehot.astype(jnp.int32), axis=0)         # (n, 3)
    before = jnp.where((seg_start > 0)[:, None],
                       counts[jnp.maximum(seg_start - 1, 0)], 0)
    own = jnp.clip(lab_s - 1, 0, 2)[:, None]
    rank_sorted = jnp.take_along_axis(counts - before, own, axis=1)[:, 0]
    ranks = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.where(lab_s != REGULAR, rank_sorted, 0), unique_indices=True)
    return ranks.reshape(field.shape)
