"""Critical point detection (paper Sec. IV-A, "CD" stage).

Each grid point of a 2-D scalar field is classified against its 4-neighbors
(top/bottom/left/right) into:

  REGULAR = 0 (00)   MINIMA = 1 (01)   SADDLE = 2 (10)   MAXIMA = 3 (11)

using *strict* comparisons.  Corner points use two neighbors and edge points
three (paper); a saddle requires both opposite pairs, so saddles are only
defined at interior points (a 3-neighbor "saddle" is ill-posed on the
4-neighborhood — documented choice).

A 3-D volume is classified over its 6 axis neighbours (the repo's
extension; the paper defines critical points in 2-D only): a minimum
(maximum) has every existing axis neighbour strictly higher (lower); a
saddle is an interior point where each axis pair lies strictly on one side
and the three axes do not all lie on the same side.  Every function below
takes either rank.

The classification is branch-free (comparison masks) and is the oracle for
the Pallas kernels in kernels/cp_detect.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

REGULAR, MINIMA, SADDLE, MAXIMA = 0, 1, 2, 3
LABEL_NAMES = {REGULAR: "regular", MINIMA: "minima", SADDLE: "saddle", MAXIMA: "maxima"}


def _shifted(field: jnp.ndarray):
    """Return (value, exists) for the t/d/l/r neighbors of every point."""
    ny, nx = field.shape
    pad = jnp.pad(field, 1, mode="edge")
    t = pad[:-2, 1:-1]
    d = pad[2:, 1:-1]
    l = pad[1:-1, :-2]
    r = pad[1:-1, 2:]
    ii = jnp.arange(ny)[:, None]
    jj = jnp.arange(nx)[None, :]
    has_t = (ii > 0) & jnp.ones((1, nx), bool)
    has_d = (ii < ny - 1) & jnp.ones((1, nx), bool)
    has_l = jnp.ones((ny, 1), bool) & (jj > 0)
    has_r = jnp.ones((ny, 1), bool) & (jj < nx - 1)
    return (t, has_t), (d, has_d), (l, has_l), (r, has_r)


def axis_neighbors(f: jnp.ndarray, axis: int):
    """(prev, next, has_prev, has_next) along one axis of a volume
    (edge-replicated values)."""
    n = f.shape[axis]
    pad = [(0, 0)] * f.ndim
    pad[axis] = (1, 1)
    p = jnp.pad(f, pad, mode="edge")
    prev = jax.lax.slice_in_dim(p, 0, n, axis=axis)
    nxt = jax.lax.slice_in_dim(p, 2, n + 2, axis=axis)
    shape = [1] * f.ndim
    shape[axis] = n
    ii = jnp.arange(n).reshape(shape)
    return (prev, nxt, jnp.broadcast_to(ii > 0, f.shape),
            jnp.broadcast_to(ii < n - 1, f.shape))


def _classify3(f: jnp.ndarray) -> jnp.ndarray:
    """6-neighbour label map of a 3-D volume."""
    is_min = is_max = interior = jnp.ones(f.shape, bool)
    all_hi = all_lo = one_sided = interior
    for ax in range(3):
        pv, nv, hp, hn = axis_neighbors(f, ax)
        is_min &= jnp.where(hp, pv > f, True) & jnp.where(hn, nv > f, True)
        is_max &= jnp.where(hp, pv < f, True) & jnp.where(hn, nv < f, True)
        interior &= hp & hn
        pair_hi, pair_lo = (pv > f) & (nv > f), (pv < f) & (nv < f)
        all_hi &= pair_hi
        all_lo &= pair_lo
        one_sided &= pair_hi | pair_lo
    is_saddle = interior & one_sided & ~all_hi & ~all_lo
    labels = jnp.where(is_min, MINIMA, REGULAR)
    labels = jnp.where(is_saddle, SADDLE, labels)
    labels = jnp.where(is_max, MAXIMA, labels)
    return labels.astype(jnp.int32)


def classify(field: jnp.ndarray) -> jnp.ndarray:
    """Label map for a 2-D field or a 3-D volume -> int32 in {0,1,2,3}."""
    f = field.astype(jnp.float32)
    if f.ndim == 3:
        return _classify3(f)
    (t, ht), (d, hd), (l, hl), (r, hr) = _shifted(f)

    # per-direction strict comparisons; a missing neighbor never vetoes.
    hi_t = jnp.where(ht, t > f, True)   # neighbor strictly higher (or absent)
    hi_d = jnp.where(hd, d > f, True)
    hi_l = jnp.where(hl, l > f, True)
    hi_r = jnp.where(hr, r > f, True)
    lo_t = jnp.where(ht, t < f, True)
    lo_d = jnp.where(hd, d < f, True)
    lo_l = jnp.where(hl, l < f, True)
    lo_r = jnp.where(hr, r < f, True)

    is_min = hi_t & hi_d & hi_l & hi_r
    is_max = lo_t & lo_d & lo_l & lo_r

    interior = ht & hd & hl & hr
    vert_hi = (t > f) & (d > f)
    vert_lo = (t < f) & (d < f)
    horz_hi = (l > f) & (r > f)
    horz_lo = (l < f) & (r < f)
    is_saddle = interior & ((vert_hi & horz_lo) | (vert_lo & horz_hi))

    labels = jnp.where(is_min, MINIMA, REGULAR)
    labels = jnp.where(is_saddle, SADDLE, labels)
    labels = jnp.where(is_max, MAXIMA, labels)
    return labels.astype(jnp.int32)


def neighbor_min_max(field: jnp.ndarray):
    """(min, max) over *available* 4-neighbors (6 in a volume) of each
    point (edge-aware)."""
    f = field.astype(jnp.float32)
    if f.ndim == 3:
        big = jnp.float32(jnp.inf)
        nmin = jnp.full(f.shape, big)
        nmax = jnp.full(f.shape, -big)
        for ax in range(3):
            pv, nv, hp, hn = axis_neighbors(f, ax)
            nmin = jnp.minimum(nmin, jnp.minimum(jnp.where(hp, pv, big),
                                                 jnp.where(hn, nv, big)))
            nmax = jnp.maximum(nmax, jnp.maximum(jnp.where(hp, pv, -big),
                                                 jnp.where(hn, nv, -big)))
        return nmin, nmax
    (t, ht), (d, hd), (l, hl), (r, hr) = _shifted(f)
    big = jnp.float32(jnp.inf)
    nmin = jnp.minimum(
        jnp.minimum(jnp.where(ht, t, big), jnp.where(hd, d, big)),
        jnp.minimum(jnp.where(hl, l, big), jnp.where(hr, r, big)))
    nmax = jnp.maximum(
        jnp.maximum(jnp.where(ht, t, -big), jnp.where(hd, d, -big)),
        jnp.maximum(jnp.where(hl, l, -big), jnp.where(hr, r, -big)))
    return nmin, nmax


def count_labels(labels: jnp.ndarray):
    """Dict of counts per class (host-friendly)."""
    return {name: int((labels == code).sum())
            for code, name in LABEL_NAMES.items()}
