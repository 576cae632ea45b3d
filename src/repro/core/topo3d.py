"""TopoSZp on 3-D volumes — names kept for one-field callers.

A volume runs through the normal pipeline (``core.toposzp``): its entry
points take (nz, ny, nx) and pick the 6-neighbour CD, CP^+RP^ and RS^
kernels by the field's rank.  The 6-neighbourhood (the repo's extension;
the paper defines critical points in 2-D only) is defined in
``core.critical_points``.
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from repro.core.critical_points import (MAXIMA, MINIMA, REGULAR, SADDLE,
                                        classify)
from repro.core.szp import DEFAULT_BLOCK
from repro.core.toposzp import (TopoSZpCompressed, toposzp_compress,
                                toposzp_decompress)

__all__ = ["REGULAR", "MINIMA", "SADDLE", "MAXIMA", "classify3d",
           "toposzp3d_compress", "toposzp3d_decompress"]


def classify3d(field: jnp.ndarray) -> jnp.ndarray:
    """6-neighbour label map of a 3-D volume -> int32 {0,1,2,3}."""
    if field.ndim != 3:
        raise ValueError(f"expected a (nz, ny, nx) volume, got {field.shape}")
    return classify(field)


def toposzp3d_compress(field: jnp.ndarray, eb: float,
                       block: int = DEFAULT_BLOCK) -> TopoSZpCompressed:
    """One volume through :func:`toposzp_compress`."""
    return toposzp_compress(field, eb, block=block)


def toposzp3d_decompress(comp: TopoSZpCompressed, shape: Sequence[int],
                         eb: float, block: int = DEFAULT_BLOCK) -> jnp.ndarray:
    """One volume through :func:`toposzp_decompress`."""
    return toposzp_decompress(comp, shape, eb, block=block)
