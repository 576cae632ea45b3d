"""Compile every Pallas kernel for a described TPU v5e (no chip needed).

Mosaic, the TPU kernel compiler, refuses what interpret mode accepts:
scalar loads from HBM, unaligned dynamic row loads, unsigned reductions,
narrowing casts of booleans, blocks too large for VMEM.  So each kernel of
the compressor's main path is compiled here for one chip of a described
``v5e:2x2`` topology, at the paper's largest grid (CESM-ATM 1800x3600:
202,752 blocks of 32 after tile padding), under ``vmap`` where the batched
APIs map it, and — for the CD and QZ+LZ kernels — at the KV-page field
shape of ``serve/paging.py`` (MiniCPM-2B: 36 KV heads x 64 dims x 16
positions).  The 6-neighbour CD, extrema-restore and Shepard kernels of
3-D volumes are compiled at SDRBench Hurricane-ISABEL's 100x500x500.
Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (bitpack_compact, bitpack_pack, cp_detect,
                           extrema_restore, rbf_refine, szp_quant)

ATM = (1800, 3600)
ISABEL = (100, 500, 500)
BLOCKS = 202_752            # ceil(1800*3600 / 32) padded to the 256-row tile
K = 32
PAGE = (36 * 64, 16)        # serve/paging.py field: (h*dh channels, page)
PAGE_BLOCKS = 1280          # 36*64*16 / 32 = 1152, padded to the 256-row tile
N = 4                       # fields per batched call

f32, i32, u32, u8 = jnp.float32, jnp.int32, jnp.uint32, jnp.uint8


def _pack(w):
    return lambda m, wd: bitpack_pack.local_pack_blocks(
        m, wd, max_width=w, interpret=False)


def _compact(local, offs, nb):
    return bitpack_compact.compact_local_blocks(local, offs, nb,
                                                interpret=False)


def _shepard(f, s, r):
    return rbf_refine.shepard_refine_global(f, s, r, interpret=False)


def _delta(q):
    return szp_quant.szp_delta_blocks(q, interpret=False)


CASES = {
    "cp_detect_atm": (lambda f: cp_detect.cp_detect(f, interpret=False),
                      [(ATM, f32)]),
    "cp_detect_page": (lambda f: cp_detect.cp_detect(f, interpret=False),
                       [(PAGE, f32)]),
    "szp_delta_atm": (_delta, [((BLOCKS, K), i32)]),
    "szp_delta_atm_batch": (jax.vmap(_delta), [((N, BLOCKS, K), i32)]),
    "szp_delta_page": (_delta, [((PAGE_BLOCKS, K), i32)]),
    "szp_dequant_atm": (
        lambda f, m, s, eb: szp_quant.szp_dequant_blocks(
            f, m, s, eb, interpret=False),
        [((BLOCKS,), i32), ((BLOCKS, K - 1), u32), ((BLOCKS, K - 1), i32),
         ((), f32)]),
    "local_pack_atm_w8": (_pack(8), [((BLOCKS, K - 1), u32),
                                     ((BLOCKS,), i32)]),
    "local_pack_atm_w32": (_pack(32), [((BLOCKS, K - 1), u32),
                                       ((BLOCKS,), i32)]),
    "compact_atm_w8": (_compact, [((BLOCKS, 31), u8), ((BLOCKS,), i32),
                                  ((BLOCKS,), i32)]),
    "compact_atm_w32": (_compact, [((BLOCKS, 124), u8), ((BLOCKS,), i32),
                                   ((BLOCKS,), i32)]),
    "compact_atm_w32_batch": (jax.vmap(_compact),
                              [((N, BLOCKS, 124), u8), ((N, BLOCKS), i32),
                               ((N, BLOCKS), i32)]),
    "extrema_restore_atm": (
        lambda r, lab, cur, rk, eb: extrema_restore.extrema_restore(
            r, lab, cur, rk, eb, interpret=False),
        [(ATM, f32), (ATM, i32), (ATM, i32), (ATM, i32), ((), f32)]),
    "shepard_atm": (_shepard, [(ATM, f32), ((), f32), ((), i32)]),
    "shepard_atm_batch": (jax.vmap(_shepard),
                          [((N,) + ATM, f32), ((N,), f32), ((N,), i32)]),
    "cp_detect_isabel": (lambda f: cp_detect.cp_detect3(f, interpret=False),
                         [(ISABEL, f32)]),
    "cp_detect_isabel_batch": (
        jax.vmap(lambda f: cp_detect.cp_detect3(f, interpret=False)),
        [((N,) + ISABEL, f32)]),
    "extrema_restore_isabel": (
        lambda r, lab, cur, rk, eb: extrema_restore.extrema_restore3(
            r, lab, cur, rk, eb, interpret=False),
        [(ISABEL, f32), (ISABEL, i32), (ISABEL, i32), (ISABEL, i32),
         ((), f32)]),
    "shepard_isabel": (_shepard, [(ISABEL, f32), ((), f32), ((), i32)]),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, args = CASES[name]
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
