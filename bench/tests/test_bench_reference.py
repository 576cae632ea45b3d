"""The benchmark's own CD classifier and readings, on the CPU."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from repro.core.critical_points import classify as program_classify


def _fields():
    rng = np.random.default_rng(12)
    yield rng.random((17, 23), dtype=np.float32)
    yield np.round(rng.random((16, 16)) * 4).astype(np.float32)    # ties
    yield rng.random((1, 9), dtype=np.float32)
    yield rng.random((9, 1), dtype=np.float32)
    yield rng.random((2, 2), dtype=np.float32)
    yield np.zeros((5, 6), np.float32)
    x = np.linspace(-1, 1, 21, dtype=np.float32)
    yield (x[:, None] ** 2 - x[None, :] ** 2)                       # saddle


@pytest.mark.parametrize("i", range(7))
def test_classifier_matches_the_programs_on_small_fields(i):
    f = list(_fields())[i]
    np.testing.assert_array_equal(
        np.asarray(reference.classify(jnp.asarray(f))),
        np.asarray(program_classify(jnp.asarray(f))))


def test_classifier_labels():
    f = np.array([[5, 5, 5], [5, 1, 5], [5, 5, 5]], np.float32)
    assert reference.classify(jnp.asarray(f))[1, 1] == reference.MINIMUM
    assert reference.classify(jnp.asarray(-f))[1, 1] == reference.MAXIMUM
    s = np.array([[0, 9, 0], [1, 5, 1], [0, 9, 0]], np.float32)
    assert reference.classify(jnp.asarray(s))[1, 1] == reference.SADDLE


def test_readings_count_false_cases_and_the_error():
    f = np.zeros((1, 6, 6), np.float32)
    f[0, 2, 2] = 1.0                                 # one maximum
    rec = f.copy()
    rec[0, 4, 4] = 0.001                             # a false maximum
    g = {"err_bound_eb": 2, "fp": 0, "ft": 0, "fn_share": 0.5}
    r = reference.readings(jnp.asarray(f), jnp.asarray(rec), 1e-3, g)
    assert r["fp"]["value"] == 1 and r["ft"]["value"] == 0
    assert r["max_err"]["value"] == pytest.approx(0.001)
    assert r["max_err"]["limit"] > 0.002
    assert not reference.passed(r)
    rec[0, 4, 4] = 0.0
    rec[0, 2, 2] = -1.0                              # maximum -> minimum
    r = reference.readings(jnp.asarray(f), jnp.asarray(rec), 1e-3, g)
    assert r["ft"]["value"] == 1 and r["max_err"]["value"] == 2.0
    assert not reference.passed(r)


def test_fn_share_is_against_the_plain_quantizer():
    rng = np.random.default_rng(3)
    f = jnp.asarray(rng.random((2, 32, 32), dtype=np.float32))
    eb = 0.05
    plain = 2 * eb * jnp.floor((f + eb) / (2 * eb))
    g = {"err_bound_eb": 1, "fn_share": 0.5}
    r = reference.readings(f, plain, eb, g)
    assert r["fn_share"]["value"] == pytest.approx(1.0)
    assert not reference.passed(r)
    r = reference.readings(f, f, eb, g)
    assert r["fn_share"]["value"] == 0 and r["max_err"]["value"] == 0
    assert reference.passed(r)


def test_szp_guarantees_check_the_bound_only():
    f = jnp.zeros((1, 4, 4))
    r = reference.readings(f, f + 0.001, 1e-3, {"err_bound_eb": 1})
    assert set(r) == {"max_err", "fields"}
    assert reference.passed(r)
    r = reference.readings(f, f + 0.0011, 1e-3, {"err_bound_eb": 1})
    assert not reference.passed(r)
    with pytest.raises(ValueError):
        reference.readings(f, jnp.zeros((2, 4, 4)), 1e-3, {"err_bound_eb": 1})
