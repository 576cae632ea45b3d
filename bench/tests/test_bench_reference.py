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


def _brute_force_3d(f):
    """Labels of a 3-D volume by a loop over points and their axis
    neighbours: the definition in ``reference._classify3`` read literally."""
    lab = np.zeros(f.shape, np.int32)
    for p in np.ndindex(*f.shape):
        v = f[p]
        nbrs, sides = [], []
        for ax in range(3):
            pair = []
            for step in (-1, 1):
                q = list(p)
                q[ax] += step
                if 0 <= q[ax] < f.shape[ax]:
                    nbrs.append(f[tuple(q)])
                    pair.append(f[tuple(q)])
            if len(pair) == 2 and min(pair) > v:
                sides.append("hi")
            elif len(pair) == 2 and max(pair) < v:
                sides.append("lo")
            else:
                sides.append(None)
        if all(n < v for n in nbrs):
            lab[p] = reference.MAXIMUM
        elif all(n > v for n in nbrs):
            lab[p] = reference.MINIMUM
        elif None not in sides and len(set(sides)) == 2:
            lab[p] = reference.SADDLE
    return lab


def _volumes():
    rng = np.random.default_rng(21)
    yield rng.random((5, 6, 7), dtype=np.float32)
    yield np.round(rng.random((6, 6, 6)) * 3).astype(np.float32)    # ties
    yield np.round(rng.random((4, 5, 3)) * 2).astype(np.float32)    # ties
    yield rng.random((1, 7, 8), dtype=np.float32)
    yield rng.random((6, 1, 5), dtype=np.float32)
    yield rng.random((2, 2, 9), dtype=np.float32)
    yield np.zeros((3, 4, 5), np.float32)
    x = np.linspace(-1, 1, 9, dtype=np.float32)
    yield (x[:, None, None] ** 2 + x[None, :, None] ** 2
           - x[None, None, :] ** 2)                                   # saddle


@pytest.mark.parametrize("i", range(8))
def test_3d_classifier_matches_a_loop_over_neighbours(i):
    f = list(_volumes())[i]
    np.testing.assert_array_equal(
        np.asarray(reference.classify(jnp.asarray(f))), _brute_force_3d(f))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_3d_classifier_matches_the_programs_topo3d(seed):
    from repro.core.topo3d import classify3d
    rng = np.random.default_rng(seed)
    f = rng.random((9, 11, 13), dtype=np.float32)
    f[::2] = np.round(f[::2] * 5) / 5                                 # ties
    got = np.asarray(reference.classify(jnp.asarray(f)))
    np.testing.assert_array_equal(got, np.asarray(classify3d(jnp.asarray(f))))
    assert set(np.unique(got)) == {0, 1, 2, 3}


def test_3d_classifier_labels_and_rank_check():
    f = np.full((3, 3, 3), 5, np.float32)
    f[1, 1, 1] = 1
    assert reference.classify(jnp.asarray(f))[1, 1, 1] == reference.MINIMUM
    assert reference.classify(jnp.asarray(-f))[1, 1, 1] == reference.MAXIMUM
    s = np.zeros((3, 3, 3), np.float32)
    s[0, 1, 1] = s[2, 1, 1] = s[1, 0, 1] = s[1, 2, 1] = 9
    s[1, 1, 0] = s[1, 1, 2] = -9                  # z, y above; x below
    assert reference.classify(jnp.asarray(s))[1, 1, 1] == reference.SADDLE
    with pytest.raises(ValueError):
        reference.classify(jnp.zeros((2, 2, 2, 2)))


def _toposzp3d(fields, eb):
    from repro.core.topo3d import toposzp3d_compress, toposzp3d_decompress
    return jnp.stack([toposzp3d_decompress(toposzp3d_compress(f, eb),
                                           f.shape, eb) for f in fields])


def test_readings_of_toposzp3d_and_its_bf16_control():
    from bench.fields import make_fields
    from bench.spec import find_cell
    cell = find_cell("atm_topo.compress")
    g, eb = cell.config["guarantees"], 1e-3
    fields = make_fields(2**33 + 17, 3, (8, 12, 16), cell.traffic["fields"])
    r = reference.readings(fields, _toposzp3d(fields, eb), eb, g)
    assert r["fp"]["value"] == 0 and r["ft"]["value"] == 0
    assert r["max_err"]["value"] <= r["max_err"]["limit"]
    assert r["max_err"]["limit"] < 2 * eb * 1.001
    assert len(r["fields"]) == 3 and sum(p["n_cp"] for p in r["fields"]) > 0
    bf16 = fields.astype(jnp.bfloat16).astype(jnp.float32)
    r = reference.readings(fields, _toposzp3d(bf16, eb), eb, g)
    assert not reference.passed(r)
