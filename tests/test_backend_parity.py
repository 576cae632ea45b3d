"""Three-way backend parity for the production compression pipeline.

The contract of the kernels.ops dispatch (ISSUE 5 tentpole): compressed
streams are BYTE-identical across ``backend={"interpret","jnp"}`` on the
CPU (the compiled "pallas" backend runs the same kernel bodies on a TPU,
where ``chip_smoke.py`` checks it against "jnp"), batched APIs equal
per-field loops, and the guarded
MXU tri-matmul dequant falls back to the exact int32 path when codes can
reach the f32-inexact >= 2^24 range.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # no network in CI: deterministic shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import io as cio
from repro.core import bitpack, false_cases_host, max_abs_error
from repro.core.szp import (_dequant_stage, compress_codes, decompress_codes,
                            szp_compress, szp_compress_batch, szp_decompress,
                            szp_decompress_batch)
from repro.core.toposzp import (batch_slice, toposzp_compress,
                                toposzp_compress_batch, toposzp_decompress,
                                toposzp_decompress_batch)
from repro.kernels import ops

BACKENDS = ("interpret", "jnp")


def _random_field(seed, shape, rough=False):
    rng = np.random.default_rng(seed)
    f = rng.standard_normal(shape).astype(np.float32)
    if not rough:
        y, x = np.meshgrid(np.linspace(0, 5, shape[0]),
                           np.linspace(0, 5, shape[1]), indexing="ij")
        f = (np.sin(x) * np.cos(y) + 0.05 * f).astype(np.float32)
    return jnp.asarray(f)


@pytest.mark.parametrize("shape,eb", [((64, 96), 1e-3), ((33, 77), 1e-2),
                                      ((7, 130), 1e-4)])
def test_szp_streams_byte_identical(shape, eb):
    x = _random_field(shape[0], shape, rough=True)
    blobs = {be: cio.serialize_szp(szp_compress(x, eb, backend=be),
                                   shape, eb) for be in BACKENDS}
    assert blobs["interpret"] == blobs["jnp"]
    for be in BACKENDS:
        rec = szp_decompress(szp_compress(x, eb, backend=be), shape, eb,
                             backend=be)
        assert float(jnp.abs(rec - x).max()) <= eb * (1 + 1e-5)


@pytest.mark.parametrize("shape,eb", [((48, 64), 1e-2), ((61, 41), 1e-3)])
def test_toposzp_streams_byte_identical_and_guaranteed(shape, eb):
    f = _random_field(shape[1], shape)
    blobs = {}
    for be in BACKENDS:
        comp = toposzp_compress(f, eb, backend=be)
        blobs[be] = cio.serialize_toposzp(comp, shape, eb)
        rec = toposzp_decompress(comp, shape, eb, backend=be)
        fc = false_cases_host(f, rec)
        assert fc["FP"] == 0 and fc["FT"] == 0, (be, fc)
        assert float(max_abs_error(f, rec)) <= 2 * eb * (1 + 1e-5)
    assert blobs["interpret"] == blobs["jnp"]


def test_extrema_and_base_bitwise_across_backends():
    """Everything before the RBF estimate is bit-identical across backends
    (the Shepard estimate itself is allclose-only: separable vs direct
    summation order)."""
    from repro.core.stencils import apply_extrema_stencils
    from repro.core.critical_points import classify
    from repro.core.quantize import quantize_roundtrip
    from repro.core.relative_order import compute_ranks
    from repro.core.quantize import quantize
    f = _random_field(3, (50, 70))
    eb = 1e-2
    recon = quantize_roundtrip(f, eb)
    labels = classify(f)
    ranks = compute_ranks(f, labels, quantize(f, eb))
    outs = [apply_extrema_stencils(recon, labels, ranks, eb, backend=be)[0]
            for be in BACKENDS]
    assert jnp.array_equal(outs[0], outs[1])
    # and the kernel-dispatched form matches the legacy jnp stencil math
    legacy, _ = apply_extrema_stencils(recon, labels, ranks, eb)
    assert jnp.array_equal(outs[1], legacy)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([1e-2, 1e-3]),
       st.sampled_from([16, 32, 64]), st.integers(1, 9),
       st.sampled_from(["smooth", "rough", "quantized", "spiky"]))
def test_property_roundtrip_and_parity(seed, eb, block, rows, kind):
    """Hypothesis sweep over (shape, eb, block, width distribution):
    bound-respecting roundtrip + byte-identical streams on every draw."""
    rng = np.random.default_rng(seed)
    shape = (rows, int(rng.integers(17, 80)))
    x = rng.uniform(-4, 4, shape).astype(np.float32)
    if kind == "quantized":          # many zero-delta / constant blocks
        x = np.round(x)
    elif kind == "spiky":            # wide width distribution in one field
        x[rng.integers(0, rows), :] *= 1e4
    elif kind == "smooth":
        x = np.cumsum(x, axis=1) * 0.01
    x = jnp.asarray(x.astype(np.float32))
    # f32 representation error dominates eb at spiky magnitudes; same
    # spacing-aware tolerance as test_szp_roundtrip.test_szp_error_bound.
    tol = eb + 4 * float(np.spacing(np.float32(float(jnp.abs(x).max()) + eb)))
    blobs = {}
    for be in ("interpret", "jnp"):
        parts = szp_compress(x, eb, block=block, backend=be)
        blobs[be] = cio.serialize_szp(parts, shape, eb, block)
        rec = szp_decompress(parts, shape, eb, block=block, backend=be)
        assert float(jnp.abs(rec - x).max()) <= tol
    assert blobs["interpret"] == blobs["jnp"]


# --------------------------------------------------------------------------
# batched APIs == per-field loops
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("interpret", "jnp"))
def test_szp_batch_equals_loop(backend):
    rng = np.random.default_rng(0)
    shape = (40, 56)
    xs = jnp.asarray(rng.standard_normal((4,) + shape).astype(np.float32))
    eb = 1e-3
    bparts = szp_compress_batch(xs, eb, backend=backend)
    outs = szp_decompress_batch(bparts, shape, eb, backend=backend)
    for i in range(xs.shape[0]):
        parts = szp_compress(xs[i], eb, backend=backend)
        sliced = jax.tree_util.tree_map(lambda a: a[i], bparts)
        assert (cio.serialize_szp(sliced, shape, eb)
                == cio.serialize_szp(parts, shape, eb))
        rec = szp_decompress(parts, shape, eb, backend=backend)
        assert jnp.array_equal(outs[i], rec)


@pytest.mark.parametrize("backend", ("interpret", "jnp"))
def test_toposzp_batch_equals_loop(backend):
    shape = (36, 44)
    fields = jnp.stack([_random_field(s, shape, rough=(s % 2 == 0))
                        for s in range(3)])
    eb = 1e-2
    bcomp = toposzp_compress_batch(fields, eb, backend=backend)
    brec = toposzp_decompress_batch(bcomp, shape, eb, backend=backend)
    for i in range(3):
        comp = toposzp_compress(fields[i], eb, backend=backend)
        assert (cio.serialize_toposzp(batch_slice(bcomp, i), shape, eb)
                == cio.serialize_toposzp(comp, shape, eb))
        rec = toposzp_decompress(batch_slice(bcomp, i), shape, eb,
                                 backend=backend)
        assert jnp.array_equal(brec[i], rec)


def test_batch_rejects_wrong_rank():
    with pytest.raises(ValueError):
        toposzp_compress_batch(jnp.zeros((8, 8)), 1e-2)


# --------------------------------------------------------------------------
# the 2^24 tri-matmul guard (ISSUE 5 satellite: regression w/ huge codes)
# --------------------------------------------------------------------------

def test_dequant_guard_falls_back_past_2p24():
    """Codes with >= 2^24 deltas: the f32 tri-matmul cumsum is INEXACT
    (demonstrated by bypassing the guard), and the guarded decompress
    routes to the int32 path so all backends stay bit-identical."""
    k = 32
    step = (1 << 24) + 1                       # not f32-representable
    codes = jnp.asarray(np.arange(64, dtype=np.int64) * step % (1 << 30),
                        dtype=jnp.int32)
    parts = compress_codes(codes, block=k)
    assert int(np.asarray(parts.widths).max()) >= 24
    eb = 1.0
    n = int(codes.shape[0])
    # exact path == dequantized true codes
    want = (codes.astype(jnp.float32) * 2.0).astype(jnp.float32)
    got_guarded = szp_decompress(parts, (1, n), eb, block=k,
                                 backend="interpret").reshape(-1)
    assert jnp.array_equal(got_guarded, want)
    # bypassing the guard hits the f32-inexact tri-matmul: different bytes
    got_raw = _dequant_stage(parts, n, eb, k, "center", "interpret")
    assert not jnp.array_equal(got_raw, want), \
        "tri-matmul unexpectedly exact; the guard test lost its teeth"


def test_toposzp_huge_dynamic_range_still_guaranteed():
    """End-to-end roundtrip whose main-stream codes exceed 2^24 (guard
    engaged inside toposzp_decompress): bound + FP/FT still hold and the
    backends still agree bit-for-bit on the stream."""
    rng = np.random.default_rng(5)
    f = jnp.asarray(rng.uniform(-8, 8, (24, 40)).astype(np.float32))
    eb = 1e-8                                   # codes ~ 4e8 >> 2^24
    blobs = {}
    for be in ("interpret", "jnp"):
        comp = toposzp_compress(f, eb, backend=be)
        blobs[be] = cio.serialize_toposzp(comp, (24, 40), eb)
        rec = toposzp_decompress(comp, (24, 40), eb, backend=be)
        fc = false_cases_host(f, rec)
        assert fc["FP"] == 0 and fc["FT"] == 0
        assert float(max_abs_error(f, rec)) <= 2 * eb * (1 + 1e-4) + 1e-6
    assert blobs["interpret"] == blobs["jnp"]


def test_rank_stream_lossless_regardless_of_backend():
    """The rank metadata decode always takes the exact int path: huge rank
    codes roundtrip exactly (lossless contract of section 7)."""
    rng = np.random.default_rng(9)
    codes = jnp.asarray(
        rng.integers(-(2 ** 28), 2 ** 28, 512, dtype=np.int64)
        .astype(np.int32))
    parts = compress_codes(codes)
    assert bool(jnp.all(decompress_codes(parts, 512) == codes))


# --------------------------------------------------------------------------
# odd-shape tile rule (ISSUE 5 satellite: shared pad-to-tile fix)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b", [1, 5, 31, 100, 129, 257, 300])
@pytest.mark.parametrize("tb", [8, 256])
def test_odd_row_counts_match_oracle(b, tb):
    rng = np.random.default_rng(b * tb)
    k = 16
    eb = 1e-3
    xb = jnp.asarray(rng.standard_normal((b, k)).astype(np.float32))
    out_k = ops.szp_quant(xb, eb, backend="interpret", tb=tb)
    out_r = ops.szp_quant(xb, eb, backend="jnp")
    for a, r, name in zip(out_k, out_r, ["first", "mags", "signs", "widths"]):
        assert a.shape == r.shape, (name, a.shape, r.shape)
        assert jnp.array_equal(a, r), name
    first, mags, signs, widths = out_r
    rec_k = ops.szp_dequant(first, mags, signs, eb, backend="interpret",
                            tb=tb)
    rec_r = ops.szp_dequant(first, mags, signs, eb, backend="jnp")
    assert rec_k.shape == rec_r.shape
    np.testing.assert_allclose(np.asarray(rec_k), np.asarray(rec_r),
                               atol=1e-6)
    mw = bitpack.width_bucket(int(widths.max()))
    lp_k = ops.local_pack(mags, widths, max_width=mw, backend="interpret",
                          tb=tb)
    lp_r = ops.local_pack(mags, widths, max_width=mw, backend="jnp")
    assert jnp.array_equal(lp_k, lp_r)


def test_row_tile_rule():
    """One rule for every wrapper: tile = min(tb, ceil(b/8)*8)."""
    assert ops._row_tile(1, 256) == 8
    assert ops._row_tile(100, 256) == 104
    assert ops._row_tile(129, 256) == 136
    assert ops._row_tile(300, 256) == 256
    assert ops._row_tile(256, 256) == 256


def test_resolve_backend(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    assert ops.resolve_backend("interpret") == "interpret"
    assert ops.resolve_backend("jnp") == "jnp"
    # off-TPU an explicit "pallas" raises (never a silent interpreter);
    # None resolves to jnp
    if jax.default_backend() != "tpu":
        with pytest.raises(ValueError, match="needs a TPU"):
            ops.resolve_backend("pallas")
        with pytest.raises(ValueError, match="needs a TPU"):
            ops.cp_detect(jnp.zeros((4, 4)), backend="pallas")
        assert ops.resolve_backend(None) == "jnp"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "jnp")
    assert ops.resolve_backend(None) == "jnp"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "bogus")
    with pytest.raises(ValueError):
        ops.resolve_backend(None)
    with pytest.raises(ValueError):
        ops.resolve_backend("bogus")
