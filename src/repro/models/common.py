"""Shared model components: sharding helper, norms, RoPE, losses, init."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# --------------------------------------------------------------------------
# Ambient-mesh sharding constraint helper
# --------------------------------------------------------------------------

_ACTIVE_MESH = None
_STRATEGY = "tp"


def set_active_mesh(mesh) -> None:
    """Register the mesh used by ``shard`` constraints (None disables).

    ``shard`` places activations with ``with_sharding_constraint``, which
    accepts only ``Auto`` mesh axes; build meshes with ``launch.mesh``."""
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def get_active_mesh():
    return _ACTIVE_MESH


def set_sharding_strategy(strategy: str) -> None:
    """'tp' (default) or 'fsdp' — under fsdp the batch shards over EVERY
    mesh axis (pure-DP activations) and dist.sharding fully shards the
    weights/optimizer instead (§Perf hillclimb knob)."""
    global _STRATEGY
    assert strategy in ("tp", "fsdp"), strategy
    _STRATEGY = strategy


def get_sharding_strategy() -> str:
    return _STRATEGY


def batch_axes():
    """Mesh axes the global batch is sharded over (pod- and strategy-aware)."""
    m = _ACTIVE_MESH
    if m is None:
        return None
    names = m.axis_names
    if _STRATEGY == "fsdp":
        return tuple(names)
    return ("pod", "data") if "pod" in names else ("data",)


def axis_size(name: str) -> int:
    """Size of a mesh axis on the active mesh (1 if unset/absent)."""
    m = _ACTIVE_MESH
    if m is None or name not in m.axis_names:
        return 1
    return m.shape[name]


def batch_axes_for(dim: int):
    """Largest batch-axis combination that divides ``dim`` evenly.

    Under fsdp on the multi-pod mesh the full set is 512-way but a
    256-sequence batch can only shard 256 ways — prefer dropping 'pod'
    first, then 'model', then 'data'."""
    axes = batch_axes()
    if axes is None:
        return None
    m = _ACTIVE_MESH
    candidates = [axes]
    if len(axes) >= 2:
        candidates.append(tuple(a for a in axes if a != "pod"))
        candidates.append(tuple(a for a in axes if a != "model"))
        candidates.append(tuple(a for a in axes
                                if a not in ("pod", "model")))
        candidates += [(a,) for a in axes]
    for c in candidates:
        if not c:
            continue
        total = math.prod(m.shape[a] for a in c)
        if total > 1 and dim % total == 0:
            return c
    return None


def shard(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """with_sharding_constraint against the active mesh (no-op if unset).

    Axis entries may be None, a mesh axis name, a tuple of names, or the
    sentinel "batch" which expands to the pod-aware batch axes.  Entries
    whose mesh axes would not divide the dimension are dropped (GSPMD would
    pad; for activations we prefer replication over padding).  Axes that
    are *manual* in the current context (inside a partial-auto shard_map,
    e.g. the compressed-DP train step) are dropped too — the constraint
    then only talks about the still-automatic axes.
    """
    if _ACTIVE_MESH is None:
        return x
    manual = set()
    try:
        am = jax.sharding.get_abstract_mesh()
        manual = set(getattr(am, "manual_axes", ()) or ())
    except Exception:
        pass
    names = set(_ACTIVE_MESH.axis_names) - manual
    resolved = []
    used = set()
    for dim, s in zip(x.shape, spec):
        if s == "batch":
            s = batch_axes_for(dim)
        if s is None:
            resolved.append(None)
            continue
        axes = (s,) if isinstance(s, str) else tuple(s)
        axes = tuple(a for a in axes if a in names and a not in used)
        if not axes:
            resolved.append(None)
            continue
        total = math.prod(_ACTIVE_MESH.shape[a] for a in axes)
        if dim % total == 0:
            resolved.append(axes)
            used.update(axes)
        else:
            resolved.append(None)
    resolved += [None] * (x.ndim - len(resolved))
    if not manual:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(_ACTIVE_MESH, P(*resolved)))
    # inside a partial-auto shard_map: constrain against the context mesh
    # (which carries the Manual/Auto axis types)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(am, P(*resolved)))


# --------------------------------------------------------------------------
# Layers
# --------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dt)


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    """Gemma-2 style logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 1e4) -> jnp.ndarray:
    """Rotary position embedding.  x: (..., S, H, Dh), positions: (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq       # (..., S, half)
    ang = ang[..., None, :]                                      # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def dense(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """x @ w with f32 accumulation (bf16-friendly)."""
    return jax.lax.dot_general(
        x, w.astype(x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


ACTS = {"silu": jax.nn.silu, "gelu": gelu, "relu": jax.nn.relu}


# --------------------------------------------------------------------------
# Chunked cross-entropy (vocab- and sequence-sharded friendly)
# --------------------------------------------------------------------------

def chunked_softmax_xent(x: jnp.ndarray, w_out: jnp.ndarray,
                         labels: jnp.ndarray, chunk: int = 2048,
                         logit_cap: Optional[float] = None,
                         real_vocab: Optional[int] = None,
                         unroll: bool = False) -> jnp.ndarray:
    """Mean token cross entropy without materializing full (T, V) logits.

    x: (B, S, d) activations, w_out: (d, V), labels: (B, S) int32.
    Scans over sequence chunks; each chunk's logits peak at (B, chunk, V).
    ``real_vocab`` masks padded vocabulary rows out of the logsumexp.
    """
    b, s, d = x.shape
    v = w_out.shape[-1]
    chunk = min(chunk, s)
    n_chunk = -(-s // chunk)
    pad = n_chunk * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    weights = (labels >= 0).astype(jnp.float32)
    labels = jnp.maximum(labels, 0)
    xc = x.reshape(b, n_chunk, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, n_chunk, chunk).transpose(1, 0, 2)
    wc = weights.reshape(b, n_chunk, chunk).transpose(1, 0, 2)

    def body(acc, inp):
        xi, li, wi = inp
        logits = dense(xi, w_out).astype(jnp.float32)
        if logit_cap is not None:
            logits = softcap(logits, logit_cap)
        if real_vocab is not None and real_vocab < v:
            logits = jnp.where(jnp.arange(v) < real_vocab, logits, -1e30)
        logits = shard(logits, "batch", None, "model")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        return acc + ((lse - gold) * wi).sum(), None

    if unroll:   # costing mode (see dryrun.py)
        total = jnp.float32(0.0)
        for i in range(n_chunk):
            total, _ = body(total, (xc[i], lc[i], wc[i]))
    else:
        total, _ = jax.lax.scan(body, jnp.float32(0.0), (xc, lc, wc))
    return total / jnp.maximum(weights.sum(), 1.0)


# --------------------------------------------------------------------------
# Init helpers
# --------------------------------------------------------------------------

def ninit(key, shape, scale: float, dtype=jnp.bfloat16) -> jnp.ndarray:
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def zinit(shape, dtype=jnp.bfloat16) -> jnp.ndarray:
    return jnp.zeros(shape, dtype)
