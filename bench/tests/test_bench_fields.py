"""Fields made on the device from a seed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.fields import make_fields, seed_key
from bench.spec import find_cell

MIX = find_cell("atm_topo.compress").traffic["fields"]


def test_same_seed_same_fields_and_large_seeds_differ():
    a = make_fields(2**33 + 1, 4, (24, 40), MIX)
    b = make_fields(2**33 + 1, 4, (24, 40), MIX)
    c = make_fields(1, 4, (24, 40), MIX)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert not np.array_equal(np.asarray(jax.random.key_data(seed_key(1))),
                              np.asarray(jax.random.key_data(seed_key(2**32 + 1))))


def test_fields_are_normalized_and_cycle_the_mix():
    f = np.asarray(make_fields(7, 4, (30, 50), MIX))
    assert f.shape == (4, 30, 50) and f.dtype == np.float32
    assert np.allclose(f.min(axis=(1, 2)), 0) and \
        np.allclose(f.max(axis=(1, 2)), 1)
    # field 3 is the second grf of the cycle, from its own key
    assert not np.array_equal(f[0], f[3])
    # vortex fields are the smoothest, multiscale the roughest
    rough = [np.abs(np.diff(x, axis=1)).mean() for x in f[:3]]
    assert rough[1] < rough[0] < rough[2]


def test_unknown_generator_is_an_error():
    with pytest.raises(ValueError):
        make_fields(0, 1, (8, 8), [{"generator": "nope"}])


def test_vortex_is_the_separable_sum_of_bumps():
    from bench import fields
    import jax
    key = jax.random.key(3)
    got = np.asarray(fields._vortex(key, (12, 20), 5))
    kc, ks, ka = jax.random.split(key, 3)
    c = np.asarray(jax.random.uniform(kc, (5, 2), jnp.float32))
    s = np.asarray(jax.random.uniform(ks, (5,), jnp.float32, 0.02, 0.12))
    a = np.asarray(jax.random.uniform(ka, (5,), jnp.float32, -1.0, 1.0))
    y, x = np.meshgrid(np.linspace(0, 1, 12), np.linspace(0, 1, 20),
                       indexing="ij")
    ref = sum(a[i] * np.exp(-((y - c[i, 0]) ** 2 + (x - c[i, 1]) ** 2)
                            / (2 * s[i] ** 2)) for i in range(5))
    ref = (ref - ref.min()) / (ref.max() - ref.min())
    np.testing.assert_allclose(got, ref, atol=1e-5)


# sha256 of make_fields(seed, 3, shape, MIX) as the 2-D-only generators
# made them, before the generators took the grid's rank from its shape
PARENT_2D = {
    (7, (30, 50)):
        "72a8055f5012fc5faf79375409c5004ca974b04612b9f43e48b0fed4b52a85f5",
    (2147480001, (36, 64)):
        "937e740596d0cf57eac4c28782df7fd256023a28d18ac8d584603cd5869f0b68",
}


@pytest.mark.parametrize("seed, shape", list(PARENT_2D))
def test_2d_fields_are_unchanged_to_the_bit(seed, shape):
    import hashlib
    f = np.asarray(make_fields(seed, 3, shape, MIX))
    assert hashlib.sha256(f.tobytes()).hexdigest() == PARENT_2D[seed, shape]


def test_3d_fields_are_normalized_deterministic_and_cycle_the_mix():
    a = np.asarray(make_fields(2**33 + 3, 4, (6, 10, 14), MIX))
    b = np.asarray(make_fields(2**33 + 3, 4, (6, 10, 14), MIX))
    c = np.asarray(make_fields(3, 4, (6, 10, 14), MIX))
    assert a.shape == (4, 6, 10, 14) and a.dtype == np.float32
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.allclose(a.min(axis=(1, 2, 3)), 0) and \
        np.allclose(a.max(axis=(1, 2, 3)), 1)
    assert not np.array_equal(a[0], a[3])
    # vortex the smoothest, multiscale the roughest, along every axis
    for axis in (1, 2, 3):
        rough = [np.abs(np.diff(x, axis=axis - 1)).mean() for x in a[:3]]
        assert rough[1] < rough[2] and rough[0] < rough[2]


@pytest.mark.parametrize("shape", [(8,), (2, 3, 4, 5)])
def test_grid_of_another_rank_is_an_error(shape):
    with pytest.raises(ValueError):
        make_fields(0, 1, shape, MIX)


def test_3d_vortex_is_the_separable_sum_of_bumps_wrapping_in_x():
    from bench import fields
    key = jax.random.key(5)
    got = np.asarray(fields._vortex(key, (6, 8, 10), 5, periodic_x=True))
    kc, ks, ka = jax.random.split(key, 3)
    c = np.asarray(jax.random.uniform(kc, (5, 3), jnp.float32))
    s = np.asarray(jax.random.uniform(ks, (5,), jnp.float32, 0.02, 0.12))
    a = np.asarray(jax.random.uniform(ka, (5,), jnp.float32, -1.0, 1.0))
    z, y, x = np.meshgrid(np.linspace(0, 1, 6), np.linspace(0, 1, 8),
                          np.arange(10) / 10, indexing="ij")
    ref = 0.0
    for i in range(5):
        dx = x - c[i, 2]
        dx = dx - np.round(dx)
        ref = ref + a[i] * np.exp(-((z - c[i, 0]) ** 2 + (y - c[i, 1]) ** 2
                                    + dx ** 2) / (2 * s[i] ** 2))
    ref = (ref - ref.min()) / (ref.max() - ref.min())
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_3d_grf_is_the_power_law_spectrum_over_every_axis():
    from bench import fields
    key = jax.random.key(2)
    got = np.asarray(fields._grf(key, (8, 12, 16), 3.0))
    white = np.asarray(jax.random.normal(key, (8, 12, 16), jnp.float32))
    fz, fy, fx = np.meshgrid(np.fft.fftfreq(8), np.fft.fftfreq(12),
                             np.fft.rfftfreq(16), indexing="ij")
    k = np.sqrt(fz ** 2 + fy ** 2 + fx ** 2)
    amp = np.where(k == 0, 0.0, np.maximum(k, 1e-6) ** -1.5)
    ref = np.fft.irfftn(np.fft.rfftn(white) * amp, s=(8, 12, 16),
                       axes=(0, 1, 2))
    ref = (ref - ref.min()) / (ref.max() - ref.min())
    np.testing.assert_allclose(got, ref, atol=1e-5)
