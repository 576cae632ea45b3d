"""RBF saddle refinement (paper Sec. IV-B, "RS-hat" stage).

Lost saddles are re-estimated from a k x k neighborhood (k in {3,5,7},
adaptive) with normalized Gaussian RBF weights — a *convex* combination
(alpha_i >= 0, sum alpha_i = 1, eq. (2) of the paper; see DESIGN.md on why
the normalized/Shepard form is the faithful realization of eq. (2)).  An
exact Gaussian-RBF interpolation solve is available as `rbf_mode="interp"`
for ablation.

Adaptive parameters (paper "Adaptive parameters" paragraph):
  * kernel width sigma in [0.5, 1.0], scaled with normalized local variation
    (smooth neighborhood -> larger sigma);
  * kernel radius r in {1,2,3} (k = 2r+1), larger when *global* variation is
    low; realized as a dynamic radius mask over a static 7x7 gather.

The update is applied only where (a) it stays within +-eb of the SZp
reconstruction (total error <= 2 eb) and (b) it actually restores the strict
saddle pattern; FP/FT suppression happens globally afterwards
(core/guarantees.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core.critical_points import SADDLE, axis_neighbors, classify
from repro.kernels import ops

MAX_RADIUS = 3  # static gather window 7x7; effective radius is dynamic


def _window_patches(field: jnp.ndarray, radius: int = MAX_RADIUS) -> jnp.ndarray:
    """(ny, nx, (2r+1)^2) neighborhood patches (edge-replicated)."""
    k = 2 * radius + 1
    pad = jnp.pad(field, radius, mode="edge")
    rows = []
    for dy in range(k):
        for dx in range(k):
            rows.append(pad[dy:dy + field.shape[0], dx:dx + field.shape[1]])
    return jnp.stack(rows, axis=-1)


def _offsets(radius: int = MAX_RADIUS) -> Tuple[jnp.ndarray, jnp.ndarray]:
    k = 2 * radius + 1
    dy, dx = jnp.meshgrid(jnp.arange(-radius, radius + 1),
                          jnp.arange(-radius, radius + 1), indexing="ij")
    return dy.reshape(k * k), dx.reshape(k * k)


def _local_range3(field: jnp.ndarray) -> jnp.ndarray:
    """max - min over each point's 3x3x3 window of a volume (edge-replicated),
    one axis at a time."""
    hi = lo = field
    for ax in range(3):
        hp, hn, _, _ = axis_neighbors(hi, ax)
        lp, ln, _, _ = axis_neighbors(lo, ax)
        hi = jnp.maximum(hi, jnp.maximum(hp, hn))
        lo = jnp.minimum(lo, jnp.minimum(lp, ln))
    return hi - lo


def adaptive_params(field: jnp.ndarray, eb: float):
    """(sigma map, radius map) from local / global variation heuristics."""
    if field.ndim == 3:
        local_var = _local_range3(field)                 # 3x3x3 variation
    else:
        patches = _window_patches(field, 1)              # 3x3 local variation
        local_var = patches.max(-1) - patches.min(-1)
    scale = jnp.maximum(field.max() - field.min(), 1e-30)
    nv = jnp.clip(local_var / scale, 0.0, 1.0)           # normalized variation
    sigma = 1.0 - 0.5 * nv                               # in [0.5, 1.0]
    gv = jnp.clip((field.std() / scale), 0.0, 1.0)       # global variation
    # low global variation -> radius 3 (k=7); high -> radius 1 (k=3)
    radius = jnp.where(gv < 0.05, 3, jnp.where(gv < 0.2, 2, 1))
    radius = jnp.broadcast_to(radius, field.shape)
    return sigma, radius


def shepard_refine(field: jnp.ndarray, sigma: jnp.ndarray,
                   radius: jnp.ndarray) -> jnp.ndarray:
    """Convex normalized-Gaussian-RBF estimate of every point from its
    neighborhood (center excluded).  Returns the refined value map."""
    patches = _window_patches(field, MAX_RADIUS)         # (ny, nx, 49)
    dy, dx = _offsets(MAX_RADIUS)
    dist2 = (dy ** 2 + dx ** 2).astype(jnp.float32)      # (49,)
    center = dist2 == 0
    w = jnp.exp(-dist2[None, None, :] / (2.0 * sigma[..., None] ** 2))
    within = (jnp.maximum(jnp.abs(dy), jnp.abs(dx))[None, None, :]
              <= radius[..., None])
    w = jnp.where(center[None, None, :] | ~within, 0.0, w)
    wsum = jnp.maximum(w.sum(-1), 1e-30)
    return (w * patches).sum(-1) / wsum                   # convex combination


def interp_refine(field: jnp.ndarray, sigma: jnp.ndarray,
                  saddle_mask: jnp.ndarray, radius_static: int = 1) -> jnp.ndarray:
    """Exact Gaussian-RBF interpolation solve per lost saddle (ablation mode).

    Solves Phi w = f over the (2r+1)^2 - 1 neighbors and evaluates at the
    center.  O(k^6) per point — run only at flagged points, scattered back.
    """
    k = 2 * radius_static + 1
    m = k * k
    patches = _window_patches(field, radius_static)       # (ny, nx, m)
    dy, dx = _offsets(radius_static)
    keep = ~((dy == 0) & (dx == 0))
    dyk, dxk = dy[keep], dx[keep]
    vals = patches[..., keep]                             # (ny, nx, m-1)
    # pairwise kernel matrix between neighbor offsets (same for all points)
    d2 = (dyk[:, None] - dyk[None, :]) ** 2 + (dxk[:, None] - dxk[None, :]) ** 2
    s2 = jnp.maximum(sigma, 0.5) ** 2                     # (ny, nx)
    phi = jnp.exp(-d2[None, None] / (2.0 * s2[..., None, None]))
    phi = phi + 1e-4 * jnp.eye(m - 1)[None, None]         # ridge for stability
    w = jnp.linalg.solve(phi, vals[..., None])[..., 0]    # (ny, nx, m-1)
    phi0 = jnp.exp(-(dyk ** 2 + dxk ** 2)[None, None] / (2.0 * s2[..., None]))
    est = (w * phi0).sum(-1)
    return jnp.where(saddle_mask, est, field)


def global_shepard_params(field: jnp.ndarray, eb: float):
    """Scalar (sigma, radius) for the separable kernel path: the adaptive
    sigma law collapsed to its field mean, radius from the (already
    global) variation rule.  Traced scalars — no static recompiles."""
    sigma, radius = adaptive_params(field, eb)
    return jnp.mean(sigma), radius.reshape(-1)[0]


def refine_saddles(recon: jnp.ndarray, labels: jnp.ndarray, eb: float,
                   rbf_mode: str = "shepard",
                   backend: Optional[str] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Refine lost saddles; returns (field, applied mask).

    ``backend=None`` keeps the per-point-adaptive pure-jnp estimator; a
    kernels.ops backend runs the separable global-parameter Shepard kernel
    (``rbf_mode="shepard"`` only — "interp" always takes the jnp solve).
    The 2*eb clamp and the restored-saddle check are identical either way,
    so the TopoSZp guarantees are estimator-independent.  A 3-D volume
    takes the kernel path only (its third pass runs along z).
    """
    recon = recon.astype(jnp.float32)
    if recon.ndim == 3 and (backend is None or rbf_mode != "shepard"):
        raise ValueError("a 3-D volume's saddles are refined by the "
                         "separable Shepard kernel: pass a backend")
    if backend is not None and rbf_mode == "shepard":
        cur = ops.cp_detect(recon, backend=backend)
        lost = (labels == SADDLE) & (cur != SADDLE)
        sigma_g, radius_g = global_shepard_params(recon, eb)
        est = ops.shepard_refine(recon, sigma_g, radius_g, backend=backend)
        cand_val = jnp.clip(est, recon - eb, recon + eb)
        cand = jnp.where(lost, cand_val, recon)
        ok = lost & (ops.cp_detect(cand, backend=backend) == SADDLE)
        return jnp.where(ok, cand, recon), ok
    cur = classify(recon)
    lost = (labels == SADDLE) & (cur != SADDLE)

    sigma, radius = adaptive_params(recon, eb)
    if rbf_mode == "shepard":
        est = shepard_refine(recon, sigma, radius)
    elif rbf_mode == "interp":
        est = interp_refine(recon, sigma, lost)
    else:
        raise ValueError(f"unknown rbf_mode: {rbf_mode}")

    # hard 2eb guarantee: movement capped at +-eb around the SZp recon
    cand_val = jnp.clip(est, recon - eb, recon + eb)
    cand = jnp.where(lost, cand_val, recon)

    # keep only updates that actually restore the strict saddle pattern
    new_labels = classify(cand)
    ok = lost & (new_labels == SADDLE)
    out = jnp.where(ok, cand, recon)
    return out, ok
