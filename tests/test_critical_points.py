"""CD-stage tests: classification semantics + edge handling."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # no network in CI: deterministic shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.critical_points import (MAXIMA, MINIMA, REGULAR, SADDLE,
                                        classify, count_labels,
                                        neighbor_min_max)


def test_single_maximum():
    f = jnp.asarray(np.array([[0, 0, 0], [0, 5, 0], [0, 0, 0]], np.float32))
    lab = classify(f)
    assert int(lab[1, 1]) == MAXIMA


def test_single_minimum():
    f = jnp.asarray(np.array([[1, 1, 1], [1, -5, 1], [1, 1, 1]], np.float32))
    lab = classify(f)
    assert int(lab[1, 1]) == MINIMA


def test_saddle():
    # t,d higher; l,r lower
    f = jnp.asarray(np.array([[9, 5, 9], [1, 3, 1], [9, 5, 9]], np.float32))
    lab = classify(f)
    assert int(lab[1, 1]) == SADDLE


def test_flat_is_regular():
    f = jnp.zeros((5, 7))
    assert bool(jnp.all(classify(f) == REGULAR))


def test_corner_extrema_use_available_neighbors():
    f = jnp.asarray(np.array([[5, 1], [1, 0]], np.float32))
    lab = classify(f)
    assert int(lab[0, 0]) == MAXIMA      # 2-neighbor corner max
    assert int(lab[1, 1]) == MINIMA


def test_paper_fig2_flattening():
    """Center 0.012 vs neighbors 0.01 is a maximum; quantization at
    eps=0.01 flattens it (FN) — the paper's motivating example."""
    from repro.core.quantize import quantize_roundtrip
    f = np.full((3, 3), 0.01, np.float32)
    f[1, 1] = 0.012
    f = jnp.asarray(f)
    assert int(classify(f)[1, 1]) == MAXIMA
    rec = quantize_roundtrip(f, 0.01)
    assert int(classify(rec)[1, 1]) == REGULAR


def test_neighbor_min_max_edges():
    f = jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4))
    nmin, nmax = neighbor_min_max(f)
    assert float(nmin[0, 0]) == 1.0       # right neighbor
    assert float(nmax[0, 0]) == 4.0       # down neighbor
    assert float(nmax[2, 3]) == 10.0


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 10_000))
def test_property_kernel_matches_core(seed):
    """Pallas cp_detect kernel == core classify on random fields."""
    from repro.kernels import ops
    rng = np.random.default_rng(seed)
    ny, nx = rng.integers(3, 40), rng.integers(3, 40)
    f = jnp.asarray(rng.standard_normal((ny, nx)).astype(np.float32))
    assert bool(jnp.all(ops.cp_detect(f, backend="interpret") == classify(f)))


def _ranks_reference(f, lab, q):
    """RP ranks by a plain lexsort over (bin, type, value with minima
    negated, index): the rank of a critical point is its 1-based position
    among the critical points of its (bin, type) group."""
    f, lab, q = (np.asarray(a).reshape(-1) for a in (f, lab, q))
    sec = np.where(lab == MINIMA, -f, f)
    order = np.lexsort((np.arange(f.size), sec, lab, q))
    ranks = np.zeros(f.size, np.int32)
    prev, r = None, 0
    for i in order:
        if lab[i] == REGULAR:
            continue
        r = r + 1 if (q[i], lab[i]) == prev else 1
        prev = (q[i], lab[i])
        ranks[i] = r
    return ranks


RANK_KINDS = ["random", "ties", "signed_zeros", "random_labels",
              "vmapped_ties", "all_critical", "all_regular"]


def _rank_case(rng, kind, shape):
    """(field, labels) of one test field of ``kind``."""
    f = rng.standard_normal(shape).astype(np.float32)
    if kind in ("ties", "vmapped_ties"):
        f = np.round(f * 4) / 4
    elif kind == "signed_zeros":
        f = np.round(f * 2) / 2
        f[rng.random(f.shape) < 0.3] = -0.0
        f[rng.random(f.shape) < 0.2] = 0.0
    elif kind == "all_critical":
        # distinct values 0.5 apart: one point per bin at eb <= 0.1
        f = (rng.permutation(f.size).reshape(shape) / 2).astype(np.float32)
    if kind == "random_labels":
        lab = rng.integers(0, 4, shape)
    elif kind == "all_critical":
        lab = rng.integers(1, 4, shape)
    elif kind == "all_regular":
        lab = np.zeros(shape)
    else:
        lab = classify(jnp.asarray(f))
    return f, jnp.asarray(lab, jnp.int32)


@pytest.mark.parametrize("kind", RANK_KINDS)
def test_compute_ranks_matches_lexsort_reference(kind):
    import jax
    from repro.core.quantize import quantize
    from repro.core.relative_order import compute_ranks
    batch = 2 if kind == "vmapped_ties" else 1

    def ranks(f, lab, eb):
        return compute_ranks(f, lab, quantize(f, eb))
    ranks_of = jax.jit(jax.vmap(ranks, in_axes=(0, 0, None)) if batch > 1
                       else ranks)
    # all_critical: one point per bin, then every point in a single bin
    ebs = (1e-1, 1e4) if kind == "all_critical" else (1e-1, 1e-2, 1e-4)
    rng = np.random.default_rng(RANK_KINDS.index(kind))
    for shape in ((3, 5), (17, 23), (40, 33)) * 2:
        cases = [_rank_case(rng, kind, shape) for _ in range(batch)]
        fj = jnp.asarray(np.stack([f for f, _ in cases]))
        lab = jnp.stack([lab for _, lab in cases])
        if batch == 1:
            fj, lab = fj[0], lab[0]
        for eb in ebs:
            got = np.asarray(ranks_of(fj, lab, eb)).reshape(batch, -1)
            for b, (f, lab_b) in enumerate(cases):
                np.testing.assert_array_equal(
                    got[b], _ranks_reference(f, lab_b,
                                             quantize(jnp.asarray(f), eb)))
            if kind == "all_regular":
                assert not got.any()


@pytest.mark.parametrize("batched", [False, True])
def test_compute_ranks_lowers_without_gather_or_scatter(batched):
    """RP applies its permutations as sort payloads: the lowered program,
    alone or vmapped over a batch as pass 1 runs it, holds sorts and no
    gather or scatter (each cost several sorts' time on the TPU)."""
    import re

    import jax
    from repro.core.relative_order import compute_ranks
    shape = (2, 17, 23) if batched else (17, 23)
    f = jnp.zeros(shape, jnp.float32)
    i = jnp.zeros(shape, jnp.int32)
    fn = jax.vmap(compute_ranks) if batched else compute_ranks
    ops = set(re.findall(r"stablehlo\.\w+",
                         jax.jit(fn).lower(f, i, i).as_text()))
    assert "stablehlo.sort" in ops
    assert not ops & {"stablehlo.gather", "stablehlo.scatter"}


def test_compute_ranks_refuses_2_pow_29_points():
    """The label rides above 29 index bits of the sort payload: a field of
    2**29 points is refused from its shape alone (nothing allocated)."""
    import jax
    from repro.core.relative_order import compute_ranks
    f = jax.ShapeDtypeStruct((1, 2**29), jnp.float32)
    i = jax.ShapeDtypeStruct((1, 2**29), jnp.int32)
    with pytest.raises(ValueError, match=r"2\*\*29"):
        jax.eval_shape(compute_ranks, f, i, i)
