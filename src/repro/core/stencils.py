"""Extrema restoration stencils (paper Sec. IV-B, "CP-hat + RP-hat" stage).

A minimum lost to quantization is pushed delta ULPs *below* the minimum of
its available neighbors; a lost maximum delta ULPs *above* the maximum
(delta = the stored same-bin rank).  "delta times machine epsilon" is
realized as delta steps in the monotone IEEE-754 integer ordering (exact,
deterministic — see DESIGN.md notes).

Corrections that would exceed the relaxed bound (|cand - recon_szp| <= eb,
hence |cand - orig| <= 2 eb) are skipped — the point stays an FN rather than
violating the bound (paper: "we deliberately avoid such situations").
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core.critical_points import MAXIMA, MINIMA, classify, neighbor_min_max
from repro.kernels import ops
from repro.utils import ulp_step


def apply_extrema_stencils(recon: jnp.ndarray, labels: jnp.ndarray,
                           ranks: jnp.ndarray, eb: float,
                           backend: Optional[str] = None,
                           cur: Optional[jnp.ndarray] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Restore lost extrema on the SZp reconstruction.

    Args:
      recon:  SZp-decompressed field (|recon - orig| <= eb), (ny, nx) or
              a (nz, ny, nx) volume (6-neighbourhood).
      labels: original CD labels from the stream, same shape.
      ranks:  same-bin ranks from the stream (delta in the paper).
      eb:     the user error bound eps (correction budget is +-eb on top).
      backend: None keeps the legacy pure-jnp math; a kernels.ops backend
        dispatches the CP^ reclassification and the fused extrema stencil
        through the kernel suite (bit-identical to the jnp math).
      cur:    precomputed ``classify(recon)`` labels, if the caller has them.

    Returns:
      (corrected field, bool mask of applied corrections)
    """
    if backend is not None:
        return _apply_extrema_stencils_ops(recon, labels, ranks, eb,
                                           backend, cur)
    recon = recon.astype(jnp.float32)
    cur = classify(recon) if cur is None else cur
    is_min = labels == MINIMA
    is_max = labels == MAXIMA
    is_cp = labels != 0
    lost_min = is_min & (cur != MINIMA)
    lost_max = is_max & (cur != MAXIMA)

    nmin, nmax = neighbor_min_max(recon)
    delta = jnp.maximum(ranks, 1)
    tgt_min = ulp_step(nmin, -delta)          # strictly below all neighbors
    tgt_max = ulp_step(nmax, +delta)          # strictly above all neighbors

    # relaxed-but-strict bound: only apply if the target stays within
    # recon +- eb (=> total error <= 2 eb).
    ok_min = lost_min & (tgt_min >= recon - eb) & (tgt_min <= recon + eb)
    ok_max = lost_max & (tgt_max >= recon - eb) & (tgt_max <= recon + eb)

    out = jnp.where(ok_min, tgt_min, recon)
    out = jnp.where(ok_max, tgt_max, out)

    # RP separation for SURVIVING critical points (paper Sec. III-C /
    # Fig. 5): same-bin CPs reconstruct to the same center, erasing their
    # ordering; move each by its rank in ULPs (maxima/saddles up, minima
    # down — rank directions chosen in relative_order.py so this restores
    # the original order).  ULP-scale: never threatens the 2 eb bound.
    survive = is_cp & ~(ok_min | ok_max)
    sep = jnp.where(is_min, -delta, delta)
    out = jnp.where(survive, ulp_step(out, sep), out)
    return out, (ok_min | ok_max | survive)


def _apply_extrema_stencils_ops(recon, labels, ranks, eb: float,
                                backend: str, cur=None):
    """Kernel-dispatched form: the fused stencil (kernels/extrema_restore)
    restores lost extrema; the RP separation for surviving CPs rides on
    top.  An applied correction always moves the value (a lost minimum has
    nmin <= recon, so its target sits strictly below recon; dually for
    maxima), so ``ext != recon`` recovers the applied mask exactly."""
    recon = recon.astype(jnp.float32)
    cur = ops.cp_detect(recon, backend=backend) if cur is None else cur
    ext = ops.extrema_restore(recon, labels, cur, ranks, eb, backend=backend)
    applied = ext != recon
    is_cp = labels != 0
    delta = jnp.maximum(ranks, 1)
    survive = is_cp & ~applied
    sep = jnp.where(labels == MINIMA, -delta, delta)
    out = jnp.where(survive, ulp_step(ext, sep), ext)
    return out, (applied | survive)
