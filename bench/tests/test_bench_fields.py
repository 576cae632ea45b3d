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
    got = np.asarray(fields._vortex(key, 12, 20, 5))
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
