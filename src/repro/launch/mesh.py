"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (data, model).
Multi-pod: 2x16x16 = 512 chips (pod, data, model); the 'pod' axis carries
the data-parallel dimension across the inter-pod links (DCN on real
hardware), which the dry-run proves shards correctly.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the models place activations
    with ``with_sharding_constraint``, which only ``Auto`` axes accept
    (``make_mesh`` defaults to ``Explicit``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(n_data: int = 4, n_model: int = 2, *, multi_pod: bool = False):
    """Small mesh for CPU tests (requires forced host device count)."""
    if multi_pod:
        return _auto_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))
