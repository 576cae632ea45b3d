"""Per-kernel shape/dtype sweeps: interpret-mode Pallas vs ref.py oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.critical_points import classify
from repro.core.quantize import quantize_roundtrip
from repro.kernels import ops

SEEDS = [0, 1]


@pytest.mark.parametrize("b,k", [(64, 32), (100, 32), (256, 16), (31, 8),
                                 (512, 64)])
@pytest.mark.parametrize("eb", [1e-2, 1e-4])
def test_szp_quant_kernel(b, k, eb):
    rng = np.random.default_rng(b * k)
    x = jnp.asarray(rng.standard_normal((b, k)).astype(np.float32) * 10)
    out_k = ops.szp_quant(x, eb, backend="interpret")
    out_r = ops.szp_quant(x, eb, backend="jnp")
    for a, r, name in zip(out_k, out_r, ["first", "mags", "signs", "widths"]):
        assert jnp.array_equal(a, r), name


@pytest.mark.parametrize("b,k", [(64, 32), (100, 32), (33, 8)])
def test_szp_dequant_kernel(b, k):
    rng = np.random.default_rng(b + k)
    eb = 1e-3
    x = jnp.asarray(rng.standard_normal((b, k)).astype(np.float32))
    first, mags, signs, widths = ops.szp_quant(x, eb, backend="jnp")
    rec_k = ops.szp_dequant(first, mags, signs, eb, backend="interpret")
    rec_r = ops.szp_dequant(first, mags, signs, eb, backend="jnp")
    np.testing.assert_allclose(np.asarray(rec_k), np.asarray(rec_r),
                               atol=1e-6)
    # and the fused roundtrip respects the error bound
    assert float(jnp.abs(rec_k - x).max()) <= eb * (1 + 1e-5)


@pytest.mark.parametrize("shape", [(64, 64), (100, 130), (7, 9), (128, 256),
                                   (3, 3)])
def test_cp_detect_kernel(shape):
    rng = np.random.default_rng(shape[0])
    f = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    assert bool(jnp.all(ops.cp_detect(f, backend="interpret") == classify(f)))


@pytest.mark.parametrize("shape", [(64, 64), (100, 130), (30, 257)])
@pytest.mark.parametrize("eb", [1e-2, 5e-2])
def test_extrema_restore_kernel(shape, eb):
    rng = np.random.default_rng(shape[1])
    f = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    recon = quantize_roundtrip(f, eb)
    labels, cur = classify(f), classify(recon)
    ranks = jnp.asarray(rng.integers(1, 9, shape).astype(np.int32))
    out_k = ops.extrema_restore(recon, labels, cur, ranks, eb,
                                backend="interpret")
    out_r = ops.extrema_restore(recon, labels, cur, ranks, eb, backend="jnp")
    assert jnp.array_equal(out_k, out_r), float(jnp.abs(out_k - out_r).max())


@pytest.mark.parametrize("shape", [(64, 64), (50, 70), (33, 129)])
@pytest.mark.parametrize("sigma,radius", [(0.75, 2), (0.5, 1), (1.0, 3)])
def test_shepard_kernel(shape, sigma, radius):
    rng = np.random.default_rng(int(sigma * 100))
    f = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    out_k = ops.shepard_refine(f, sigma, radius, backend="interpret")
    out_r = ops.shepard_refine(f, sigma, radius, backend="jnp")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)


def test_bad_backend_raises():
    with pytest.raises(ValueError):
        ops.cp_detect(jnp.zeros((4, 4)), backend="bogus")


def _volume(shape, seed, ties=False):
    rng = np.random.default_rng(seed)
    f = rng.standard_normal(shape).astype(np.float32)
    if ties:
        f[::2] = np.round(f[::2] * 2) / 2
    return jnp.asarray(f)


@pytest.mark.parametrize("shape", [(8, 12, 16), (5, 9, 130), (1, 7, 8),
                                   (3, 1, 5), (2, 2, 2)])
@pytest.mark.parametrize("ties", [False, True])
def test_cp_detect_kernel_3d(shape, ties):
    f = _volume(shape, shape[2], ties)
    assert bool(jnp.all(ops.cp_detect(f, backend="interpret") == classify(f)))


@pytest.mark.parametrize("shape", [(8, 12, 16), (4, 9, 130)])
@pytest.mark.parametrize("eb", [1e-2, 5e-2])
def test_extrema_restore_kernel_3d(shape, eb):
    rng = np.random.default_rng(shape[1])
    f = _volume(shape, shape[0])
    recon = quantize_roundtrip(f, eb)
    labels, cur = classify(f), classify(recon)
    ranks = jnp.asarray(rng.integers(1, 9, shape).astype(np.int32))
    out_k = ops.extrema_restore(recon, labels, cur, ranks, eb,
                                backend="interpret")
    out_r = ops.extrema_restore(recon, labels, cur, ranks, eb, backend="jnp")
    assert jnp.array_equal(out_k, out_r), float(jnp.abs(out_k - out_r).max())
    assert not jnp.array_equal(out_k, recon)


@pytest.mark.parametrize("shape", [(8, 12, 16), (9, 10, 11)])
@pytest.mark.parametrize("sigma,radius", [(0.75, 2), (0.5, 1), (1.0, 3)])
def test_shepard_kernel_3d(shape, sigma, radius):
    """The three separable passes equal the direct 7x7x7 window form."""
    f = _volume(shape, int(sigma * 100))
    out_k = ops.shepard_refine(f, sigma, radius, backend="interpret")
    out_r = ops.shepard_refine(f, sigma, radius, backend="jnp")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)
