"""The system under test: the program's public batch entry points, called
with their default options, as users call them.

``Target`` binds one configuration's compressor, grid and error bound to
``compress``, ``decompress`` and the ``core.io`` stream format.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def batch_item(tree, i: int):
    """Field ``i`` of a batched stream (every leaf indexed on axis 0)."""
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def restack(like, items):
    """Stack per-field streams read back from bytes into the batch layout
    of ``like``, the live batched stream: each section is zero-padded at
    its end to the live capacity.  A deserialized stream holds only the
    valid prefix of each section (the rank section is trimmed to the
    blocks that carry critical points, the payload to its bucket), and a
    decoder reads zeros past that prefix, so padding changes no decoded
    value; it lets the batch entry point run the program it runs on live
    streams."""
    def leaf(live, *parts):
        want = live.shape[1:]
        rows = []
        for p in parts:
            p = np.asarray(p)
            if p.dtype != live.dtype or p.ndim != len(want) or any(
                    a > b for a, b in zip(p.shape, want)):
                raise ValueError(f"stream section {p.dtype}{p.shape} does "
                                 f"not fit {live.dtype}{want}")
            rows.append(np.pad(p, [(0, b - a) for a, b in
                                   zip(p.shape, want)]) if p.ndim else p)
        return jnp.asarray(np.stack(rows))
    return jax.tree_util.tree_map(leaf, like, *items)


class Target:
    """One configuration's compressor through its batch entry points."""

    def __init__(self, compressor: str, shape, eb: float):
        from repro.core import io as cio
        self.shape = tuple(int(s) for s in shape)
        self.eb = float(eb)
        if compressor == "toposzp":
            from repro.core.toposzp import (toposzp_compress_batch,
                                            toposzp_decompress_batch)
            self._compress = toposzp_compress_batch
            self._decompress = toposzp_decompress_batch
            self._serialize = cio.serialize_toposzp
            self._deserialize = cio.deserialize_toposzp
        elif compressor == "szp":
            from repro.core.szp import szp_compress_batch, szp_decompress_batch
            self._compress = szp_compress_batch
            self._decompress = szp_decompress_batch
            self._serialize = cio.serialize_szp
            self._deserialize = cio.deserialize_szp
        else:
            raise ValueError(f"unknown compressor {compressor!r}")

    def compress(self, fields):
        return self._compress(fields, self.eb)

    def decompress(self, comp):
        return self._decompress(comp, self.shape, self.eb)

    def serialize(self, comp) -> list:
        """The bytes a user stores: one stream per field."""
        n = jax.tree_util.tree_leaves(comp)[0].shape[0]
        return [self._serialize(batch_item(comp, i), self.shape, self.eb)
                for i in range(n)]

    def deserialize(self, blobs, like):
        """Streams read back from ``blobs``, stacked like ``like``."""
        items = []
        for b in blobs:
            comp, shape, eb, _ = self._deserialize(b)
            if tuple(shape) != self.shape or eb != self.eb:
                raise ValueError(f"stream header {shape}, eb {eb} != "
                                 f"{self.shape}, eb {self.eb}")
            items.append(comp)
        return restack(like, items)
