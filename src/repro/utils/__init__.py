"""Small shared helpers used across the repro framework."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

# <repo>/.jax_cache: a fixed path, because the cache directory is part of
# every entry's key; a directory that moves between runs never hits.
_REPO_CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "..", "..", "..", ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself) and nothing else is set.  Otherwise the cache lives in the
    fixed in-repo ``.jax_cache`` directory (gitignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(_REPO_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cdiv(a: int, b: int) -> int:
    """Ceiling division for Python ints (static shapes)."""
    return -(-a // b)


def pad_to_multiple(x: jnp.ndarray, multiple: int, axis: int = 0,
                    mode: str = "edge") -> jnp.ndarray:
    """Pad ``x`` along ``axis`` so its length is a multiple of ``multiple``.

    ``mode='edge'`` replicates the final element so that block-delta streams
    see zero deltas in the padding region (maximally compressible).
    """
    n = x.shape[axis]
    target = cdiv(n, multiple) * multiple
    if target == n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - n)
    return jnp.pad(x, pad, mode=mode)


def bitwidth(m: jnp.ndarray, max_bits: int = 32) -> jnp.ndarray:
    """Number of bits needed to represent unsigned magnitudes ``m``.

    bitwidth(0) == 0, bitwidth(1) == 1, bitwidth(2..3) == 2, ...
    Branch-free: counts how many powers of two are <= m.
    """
    m = m.astype(jnp.uint32)
    thresh = (jnp.uint32(1) << jnp.arange(max_bits, dtype=jnp.uint32))
    return (m[..., None] >= thresh).sum(axis=-1).astype(jnp.int32)


def exclusive_cumsum(x: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    inc = jnp.cumsum(x, axis=axis)
    return inc - x


# --- monotone IEEE-754 <-> sortable-int mapping (for ULP arithmetic) -------

def float_to_ordered_int(x: jnp.ndarray) -> jnp.ndarray:
    """Map float32 -> int32 such that the int order equals the float order.

    Standard trick: for negative floats flip all bits, for positive set the
    sign bit. Total order matches IEEE-754 (with -0.0 < +0.0 collapsing to
    adjacent codes, which is harmless for our strict-inequality use).
    """
    i = x.astype(jnp.float32).view(jnp.int32)
    int32_min = jnp.int32(-(2 ** 31))
    return jnp.where(i < 0, int32_min - i, i)


def ordered_int_to_float(i: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`float_to_ordered_int`."""
    int32_min = jnp.int32(-(2 ** 31))
    raw = jnp.where(i < 0, int32_min - i, i)
    return raw.view(jnp.float32)


def ulp_step(x: jnp.ndarray, steps: jnp.ndarray) -> jnp.ndarray:
    """Move ``x`` by ``steps`` representable float32 values (monotone).

    steps > 0 moves up, steps < 0 moves down.  This realizes the paper's
    "delta times machine epsilon" stencil offset exactly (see DESIGN.md).
    """
    return ordered_int_to_float(float_to_ordered_int(x) + steps.astype(jnp.int32))


def np_bytes_concat(arrays) -> bytes:
    """Serialize a list of numpy arrays to a flat byte string."""
    return b"".join(np.asarray(a).tobytes() for a in arrays)
