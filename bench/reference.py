"""The plain reference that decides ``correct``.

It imports nothing of the program under test.  It holds a reconstruction
to the guarantees its configuration states:

* ``max_err``: max |rec - orig| over the call's fields, against the
  stated bound (``err_bound_eb`` x eb) plus 4 ulps of f32(max|x| + bound).
  The bound holds in exact arithmetic; an f32 quantizer rounds the value,
  the bin and the reconstruction, and the TPU divides by multiplying with
  the reciprocal, so the check leaves the rounding of the values' own
  magnitude.
* ``fp``, ``ft``: false critical points and false types against the
  original field's critical points, from this module's own classifier
  (strict comparisons; on a 2-D field 4 neighbours, the paper's CD
  definition; on a 3-D volume 6 neighbours, the repo's axis-by-axis
  extension of it, since the paper defines no 3-D critical points).
* ``fn_share``: critical points lost by the reconstruction, as a share of
  those lost by the plain linear quantizer at the same bound
  (``2 eb * floor((x + eb) / 2eb)``, SZp's reconstruction).  TopoSZp's
  restore stage exists to keep that share low.

Every reading is printed beside its limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

REGULAR, MINIMUM, SADDLE, MAXIMUM = 0, 1, 2, 3


def classify(f: jnp.ndarray) -> jnp.ndarray:
    """Critical-point labels (int32, values above) of a 2-D field or a 3-D
    volume, by its rank."""
    if f.ndim == 2:
        return _classify2(f)
    if f.ndim == 3:
        return _classify3(f)
    raise ValueError(f"field of shape {f.shape} is neither 2-D nor 3-D")


def _classify2(f: jnp.ndarray) -> jnp.ndarray:
    """Critical-point labels of a 2-D field.

    A point is a minimum (maximum) when every neighbour it has among
    up, down, left and right is strictly higher (lower); corners have two
    neighbours and edges three.  An interior point is a saddle when one
    opposite pair is strictly higher and the other strictly lower.  A
    maximum or minimum test wins over the saddle test (they cannot both
    hold)."""
    f = f.astype(jnp.float32)
    ny, nx = f.shape
    inf = jnp.float32(jnp.inf)
    col = jnp.full((ny, 1), inf)
    row = jnp.full((1, nx), inf)
    # neighbour values, +inf / -inf where the neighbour is missing
    up_hi = jnp.concatenate([row, f[:-1]], axis=0)
    dn_hi = jnp.concatenate([f[1:], row], axis=0)
    lf_hi = jnp.concatenate([col, f[:, :-1]], axis=1)
    rt_hi = jnp.concatenate([f[:, 1:], col], axis=1)
    up_lo = jnp.concatenate([-row, f[:-1]], axis=0)
    dn_lo = jnp.concatenate([f[1:], -row], axis=0)
    lf_lo = jnp.concatenate([-col, f[:, :-1]], axis=1)
    rt_lo = jnp.concatenate([f[:, 1:], -col], axis=1)
    is_min = (up_hi > f) & (dn_hi > f) & (lf_hi > f) & (rt_hi > f)
    is_max = (up_lo < f) & (dn_lo < f) & (lf_lo < f) & (rt_lo < f)
    ii = jnp.arange(ny)[:, None]
    jj = jnp.arange(nx)[None, :]
    interior = (ii > 0) & (ii < ny - 1) & (jj > 0) & (jj < nx - 1)
    vert_hi = (up_lo > f) & (dn_lo > f)
    vert_lo = (up_hi < f) & (dn_hi < f)
    horz_hi = (lf_lo > f) & (rt_lo > f)
    horz_lo = (lf_hi < f) & (rt_hi < f)
    is_saddle = interior & ((vert_hi & horz_lo) | (vert_lo & horz_hi))
    lab = jnp.where(is_saddle, SADDLE, REGULAR)
    lab = jnp.where(is_min, MINIMUM, lab)
    lab = jnp.where(is_max, MAXIMUM, lab)
    return lab.astype(jnp.int32)


def _neighbours(f: jnp.ndarray, axis: int, missing: float):
    """The previous and the next value along ``axis`` of every point,
    ``missing`` where the point has no such neighbour."""
    n = f.shape[axis]
    edge = list(f.shape)
    edge[axis] = 1
    pad = jnp.full(edge, missing, f.dtype)
    prev = jnp.concatenate(
        [pad, jax.lax.slice_in_dim(f, 0, n - 1, axis=axis)], axis=axis)
    nxt = jnp.concatenate(
        [jax.lax.slice_in_dim(f, 1, n, axis=axis), pad], axis=axis)
    return prev, nxt


def _classify3(f: jnp.ndarray) -> jnp.ndarray:
    """Critical-point labels of a 3-D volume, over the 6 axis neighbours.

    The paper defines critical points in 2-D only; this is the repo's
    axis-by-axis extension of its definition.  A point is a minimum
    (maximum) when every axis neighbour it has is strictly higher
    (lower).  A point is a saddle when, along each of the three axes,
    both neighbours exist and lie strictly on one side of it, and the
    three axes do not all lie on the same side.  Saddles need both
    neighbours on every axis, so only interior points can be saddles."""
    f = f.astype(jnp.float32)
    inf = jnp.float32(jnp.inf)
    is_min = is_max = True
    pair_hi, pair_lo = [], []
    for axis in range(3):
        prev_hi, next_hi = _neighbours(f, axis, inf)      # missing: +inf
        prev_lo, next_lo = _neighbours(f, axis, -inf)     # missing: -inf
        is_min = is_min & (prev_hi > f) & (next_hi > f)
        is_max = is_max & (prev_lo < f) & (next_lo < f)
        pair_hi.append((prev_lo > f) & (next_lo > f))
        pair_lo.append((prev_hi < f) & (next_hi < f))
    one_sided = [hi | lo for hi, lo in zip(pair_hi, pair_lo)]
    is_saddle = (one_sided[0] & one_sided[1] & one_sided[2]
                 & ~(pair_hi[0] & pair_hi[1] & pair_hi[2])
                 & ~(pair_lo[0] & pair_lo[1] & pair_lo[2]))
    lab = jnp.where(is_saddle, SADDLE, REGULAR)
    lab = jnp.where(is_min, MINIMUM, lab)
    lab = jnp.where(is_max, MAXIMUM, lab)
    return lab.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bound_eb",))
def _field_counts(orig, rec, eb, bound_eb: float):
    """Per-field readings: max|err|, max|x|, FP, FT, FN, FN of the plain
    quantizer."""
    orig = orig.astype(jnp.float32)
    rec = rec.astype(jnp.float32)
    lo = classify(orig)
    lr = classify(rec)
    plain = 2.0 * eb * jnp.floor((orig + eb) / (2.0 * eb))
    lp = classify(plain)
    crit = lo != REGULAR
    return dict(
        err=jnp.abs(rec - orig).max(),
        xmax=jnp.abs(orig).max(),
        fp=((~crit) & (lr != REGULAR)).sum(),
        ft=(crit & (lr != REGULAR) & (lr != lo)).sum(),
        fn=(crit & (lr == REGULAR)).sum(),
        fn_plain=(crit & (lp == REGULAR)).sum(),
        n_cp=crit.sum())


def readings(orig: jnp.ndarray, rec: jnp.ndarray, eb: float,
             guarantees: dict) -> dict:
    """Compare a call's reconstruction (N, ny, nx) or (N, nz, ny, nx)
    with its fields.

    Runs field by field so that it fits beside whatever the process holds.
    Returns ``{name: {"value": v, "limit": l}}`` for every guarantee the
    configuration states, plus ``"fields"``, the per-field readings."""
    if orig.shape != rec.shape:
        raise ValueError(f"reconstruction {rec.shape} != fields {orig.shape}")
    bound = guarantees["err_bound_eb"] * eb
    per = [{k: v.item() for k, v in
            _field_counts(orig[i], rec[i], np.float32(eb), bound).items()}
           for i in range(orig.shape[0])]
    xmax = max(p["xmax"] for p in per)
    tol = bound + 4 * float(np.spacing(np.float32(xmax + bound)))
    out = {"max_err": {"value": max(p["err"] for p in per), "limit": tol}}
    for k in ("fp", "ft"):
        if k in guarantees:
            out[k] = {"value": sum(p[k] for p in per),
                      "limit": guarantees[k]}
    if "fn_share" in guarantees:
        fn_plain = sum(p["fn_plain"] for p in per)
        out["fn_share"] = {
            "value": sum(p["fn"] for p in per) / max(fn_plain, 1),
            "limit": guarantees["fn_share"]}
    out["fields"] = per
    return out


def passed(checks: dict) -> bool:
    """True when every compared reading is within its limit."""
    return all(c["value"] <= c["limit"] for k, c in checks.items()
               if k != "fields")
