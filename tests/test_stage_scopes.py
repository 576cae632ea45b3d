"""The ``jax.named_scope`` stage regions of the compressor's batch
programs.

The benchmark's per-layer metrics find a stage's device time by these
names on the ops' scope paths (``bench/metrics/*.py``).  A refactor that
drops or renames a scope would silently empty a metric on the chip; here
each entry program is lowered at a small grid with ``backend="jnp"`` and
every stage name its readers look for must appear on the op locations,
nested where a reader relies on the nesting.
"""
import re

import jax
import pytest

from repro.core import szp, toposzp

SHAPE = (24, 40)
EB = 1e-3


def _fields():
    return jax.random.uniform(jax.random.key(0), (2,) + SHAPE)


def _measure():
    return toposzp._measure_batch.lower(_fields(), EB, block=32,
                                        backend="jnp")


def _measure_3d():
    vols = jax.random.uniform(jax.random.key(1), (2, 4) + SHAPE)
    return toposzp._measure_batch.lower(vols, EB, block=32,
                                        backend="interpret")


def _decompress():
    comp = toposzp.toposzp_compress_batch(_fields(), EB, backend="jnp")
    return toposzp._decompress_batch.lower(
        comp, EB, shape=SHAPE, block=32, rbf_mode="shepard",
        recon="center", backend="jnp")


def _szp_pack():
    first, mags, signs, widths, _ = szp._quant_stage_batch(
        _fields(), EB, block=32, backend="jnp")
    return szp._pack_stage_batch.lower(first, mags, signs, widths,
                                       max_width=8, backend="jnp")


# program -> (stage names its readers look for, (outer, inner) nestings)
CASES = {
    "toposzp._compress_measure_batch": (
        _measure, ["toposzp.stage_detect", "toposzp.stage_cd",
                   "toposzp.stage_rp", "toposzp.stage_quant"],
        [("toposzp.stage_detect", "toposzp.stage_rp"),
         ("toposzp.stage_detect", "toposzp.stage_cd")]),
    "toposzp._compress_measure_batch_3d": (
        _measure_3d, ["toposzp.stage_detect", "toposzp.stage_cd",
                      "toposzp.stage_rp", "toposzp.stage_quant"],
        [("toposzp.stage_detect", "toposzp.stage_rp"),
         ("toposzp.stage_detect", "toposzp.stage_cd")]),
    "toposzp._decompress_batch": (
        _decompress, ["toposzp.stage_decode", "toposzp.stage_decode_md",
                      "toposzp.stage_restore"],
        [("toposzp.stage_decode", "toposzp.stage_decode_md")]),
    "szp._pack_stage_batch": (_szp_pack, ["szp.stage_pack"], []),
}


def _op_paths(lowered) -> list:
    """Scope paths of the lowered module's op locations."""
    return re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))


def _components(path: str) -> list:
    """Names on a scope path, with ``vmap(...)``-style wrappers taken off."""
    return [re.sub(r"^(?:[\w.\-]+\()+|\)+$", "", p) for p in path.split("/")]


@pytest.mark.parametrize("program", sorted(CASES))
def test_every_read_stage_scope_is_in_the_lowered_program(program):
    lower, names, nested = CASES[program]
    paths = [_components(p) for p in _op_paths(lower())]
    assert paths, "no op locations in the lowered module"
    for name in names:
        assert any(name in p for p in paths), name
    for outer, inner in nested:
        assert any(outer in p and inner in p
                   and p.index(outer) < p.index(inner) for p in paths), inner
        # an op under the inner scope is always under the outer one too
        assert all(outer in p for p in paths if inner in p), inner

