"""FP/FT suppression and error-bound enforcement (paper Sec. IV-B, end).

The paper: "to prevent introducing false positives (FP) or false types (FT),
we track whether the refinement would generate a new or different type of
critical point not present in the original critical map; if so, we suppress
the correction".

Implementation: iteratively re-classify the corrected field; wherever a
point's new label is a critical type that differs from its original label
(FP: regular -> CP, FT: CP type flip), revert every correction in its
1-neighborhood and retry.  The corrected set shrinks monotonically, so the
loop terminates (empty set = plain SZp output, which is FP/FT-free by
monotonicity, Sec. III-B); in practice it converges in 1-2 iterations.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.critical_points import REGULAR, classify

_MAX_ITERS = 32


def _dilate(mask: jnp.ndarray) -> jnp.ndarray:
    """4-neighborhood (6 in a volume) dilation of a boolean mask (plus the
    mask itself)."""
    p = jnp.pad(mask, 1, mode="constant", constant_values=False)
    if mask.ndim == 3:
        c = slice(1, -1)
        return (mask | p[:-2, c, c] | p[2:, c, c] | p[c, :-2, c]
                | p[c, 2:, c] | p[c, c, :-2] | p[c, c, 2:])
    return (mask | p[:-2, 1:-1] | p[2:, 1:-1] | p[1:-1, :-2] | p[1:-1, 2:])


def violations(field: jnp.ndarray, labels_orig: jnp.ndarray) -> jnp.ndarray:
    """Mask of FP or FT points w.r.t. the original label map."""
    lbl = classify(field)
    return (lbl != REGULAR) & (lbl != labels_orig)


@partial(jax.jit, donate_argnums=())
def enforce_no_fp_ft(base: jnp.ndarray, cand: jnp.ndarray,
                     labels_orig: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Suppress corrections until the field has zero FP and zero FT.

    Args:
      base:        plain SZp reconstruction (guaranteed FP/FT-free).
      cand:        candidate field = base + stencil/RBF corrections.
      labels_orig: original CD label map from the stream.

    Returns:
      (final field, surviving-correction mask)
    """
    base = base.astype(jnp.float32)
    cand = cand.astype(jnp.float32)
    keep0 = cand != base

    def cond(state):
        keep, it = state
        field = jnp.where(keep, cand, base)
        viol = violations(field, labels_orig)
        return jnp.any(viol) & (it < _MAX_ITERS)

    def body(state):
        keep, it = state
        field = jnp.where(keep, cand, base)
        viol = violations(field, labels_orig)
        keep = keep & ~_dilate(viol)
        return keep, it + 1

    keep, _ = jax.lax.while_loop(cond, body, (keep0, jnp.int32(0)))
    return jnp.where(keep, cand, base), keep


def enforce_error_bound(base: jnp.ndarray, cand: jnp.ndarray,
                        eb: float) -> jnp.ndarray:
    """Hard clamp: |out - base| <= eb, hence |out - orig| <= 2 eb."""
    return jnp.clip(cand, base - eb, base + eb)
