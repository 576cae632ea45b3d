"""TopoSZp on 3-D volumes through the normal entry points: the guarantees,
stream identity across backends, batches, the stream format and the
bounds of the index arithmetic at SDRBench Hurricane-ISABEL's grid."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import io as cio
from repro.core.metrics import false_cases_host
from repro.core.szp import (DEFAULT_BLOCK, _worst_payload_cap, szp_compress,
                            szp_decompress, szp_roundtrip, tri_guard_width)
from repro.core.topo3d import (MAXIMA, MINIMA, classify3d,
                               toposzp3d_compress, toposzp3d_decompress)
from repro.core.toposzp import (_measure_one, _pack_streams, batch_slice,
                                toposzp_compress, toposzp_compress_batch,
                                toposzp_decompress, toposzp_decompress_batch)

BACKENDS = ("interpret", "jnp")
ISABEL = (100, 500, 500)


def _field3d(shape=(24, 28, 32), seed=0):
    rng = np.random.default_rng(seed)
    z, y, x = np.meshgrid(np.linspace(0, 3 * np.pi, shape[0]),
                          np.linspace(0, 3 * np.pi, shape[1]),
                          np.linspace(0, 3 * np.pi, shape[2]),
                          indexing="ij")
    f = (np.sin(x) * np.cos(y) * np.sin(z)
         + 0.05 * rng.standard_normal(shape))
    return jnp.asarray(f.astype(np.float32))


def test_classify3d_extrema():
    f = np.zeros((3, 3, 3), np.float32)
    f[1, 1, 1] = 5.0
    assert int(classify3d(jnp.asarray(f))[1, 1, 1]) == MAXIMA
    f[1, 1, 1] = -5.0
    assert int(classify3d(jnp.asarray(f))[1, 1, 1]) == MINIMA


@pytest.mark.parametrize("eb", [1e-2, 1e-3])
def test_3d_guarantees(eb):
    f = _field3d()
    comp = toposzp3d_compress(f, eb)
    rec = toposzp3d_decompress(comp, f.shape, eb)
    assert float(jnp.abs(rec - f).max()) <= 2 * eb * (1 + 1e-4)
    fc = false_cases_host(f, rec)
    assert fc["FP"] == 0 and fc["FT"] == 0

    # FN reduction vs plain SZp on the same 3-D field
    rec_szp, _ = szp_roundtrip(f, eb)
    fc_szp = false_cases_host(f, rec_szp.reshape(f.shape))
    if fc_szp["FN"] > 10:
        assert fc["FN"] < fc_szp["FN"]


def test_3d_ratio_positive():
    f = _field3d(seed=3)
    comp = toposzp3d_compress(f, 1e-3)
    assert 4 * f.size / int(comp.nbytes) > 1.0


@pytest.mark.parametrize("shape,eb", [((8, 12, 16), 1e-2),
                                      ((12, 20, 24), 1e-3)])
def test_3d_streams_byte_identical_across_backends(shape, eb):
    f = _field3d(shape, seed=shape[0])
    blobs = {be: cio.serialize_toposzp(toposzp_compress(f, eb, backend=be),
                                       shape, eb) for be in BACKENDS}
    assert blobs["interpret"] == blobs["jnp"]
    for be in BACKENDS:
        comp, got_shape, _, _ = cio.deserialize_toposzp(blobs[be])
        assert got_shape == shape
        rec = toposzp_decompress(comp, shape, eb, backend=be)
        assert float(jnp.abs(rec - f).max()) <= 2 * eb * (1 + 1e-5)
        fc = false_cases_host(f, rec)
        assert fc["FP"] == 0 and fc["FT"] == 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_3d_batch_equals_one_field_calls(backend):
    shape, eb = (8, 12, 16), 1e-2
    fields = jnp.stack([_field3d(shape, seed=s) for s in range(3)])
    bcomp = toposzp_compress_batch(fields, eb, backend=backend)
    brec = toposzp_decompress_batch(bcomp, shape, eb, backend=backend)
    assert brec.shape == fields.shape
    for i in range(3):
        comp = toposzp_compress(fields[i], eb, backend=backend)
        assert (cio.serialize_toposzp(batch_slice(bcomp, i), shape, eb)
                == cio.serialize_toposzp(comp, shape, eb))
        rec = toposzp_decompress(comp, shape, eb, backend=backend)
        assert jnp.array_equal(brec[i], rec)


def test_3d_streams_round_trip_through_bytes():
    shape, eb = (6, 10, 14), 1e-3
    f = _field3d(shape, seed=5)
    comp = toposzp_compress(f, eb)
    blob = cio.serialize_toposzp(comp, shape, eb)
    back, got_shape, got_eb, block = cio.deserialize_toposzp(blob)
    assert (got_shape, got_eb, block) == (shape, eb, DEFAULT_BLOCK)
    assert cio.serialize_toposzp(back, shape, eb) == blob
    assert jnp.array_equal(toposzp_decompress(back, shape, eb),
                           toposzp_decompress(comp, shape, eb))
    parts = szp_compress(f, eb)
    sblob = cio.serialize_szp(parts, shape, eb)
    sback, got_shape, _, _ = cio.deserialize_szp(sblob)
    assert got_shape == shape and cio.serialize_szp(sback, shape, eb) == sblob
    assert jnp.array_equal(szp_decompress(sback, shape, eb),
                           szp_decompress(parts, shape, eb))
    # a volume's header carries nz: 4 bytes over the 2-D layout
    flat = cio.serialize_szp(parts, (shape[0] * shape[1], shape[2]), eb)
    assert len(sblob) == len(flat) + 4
    with pytest.raises(ValueError):
        cio.serialize_szp(parts, (f.size,), eb)


def test_2d_stream_bytes_are_unchanged():
    """A 2-D field's streams keep the version-1 layout: the checksums are
    those of the same streams written before volumes had a header of
    their own."""
    rng = np.random.default_rng(2026)
    y, x = np.meshgrid(np.linspace(0, 5, 48), np.linspace(0, 7, 64),
                       indexing="ij")
    f = jnp.asarray((np.sin(x) * np.cos(y)
                     + 0.05 * rng.standard_normal((48, 64)))
                    .astype(np.float32))
    topo = cio.serialize_toposzp(toposzp_compress(f, 1e-3), (48, 64), 1e-3)
    szp = cio.serialize_szp(szp_compress(f, 1e-3), (48, 64), 1e-3)
    assert (len(topo), hashlib.sha256(topo).hexdigest()) == (
        4775, "d81ceae6ac93d8b814362473e3bcfefe"
              "2888b28c07ffecfc1a33275124167ecc")
    assert (len(szp), hashlib.sha256(szp).hexdigest()) == (
        3613, "036d128118d358944569393c6b660c85"
              "ca4fb3e7bd5e09e4f13f0b1f72acf199")


@pytest.mark.parametrize("shape", [(5,), (2, 3, 4, 5)])
def test_entry_points_refuse_other_ranks(shape):
    with pytest.raises(ValueError):
        toposzp_compress_batch(jnp.zeros((2,) + shape), 1e-2)
    with pytest.raises(ValueError):
        toposzp_compress(jnp.zeros(shape), 1e-2)
    comp = toposzp_compress_batch(jnp.zeros((2, 4, 4)), 1e-2)
    with pytest.raises(ValueError):
        toposzp_decompress_batch(comp, shape, 1e-2)
    with pytest.raises(ValueError):
        toposzp_decompress(batch_slice(comp, 0), shape, 1e-2)


def test_index_bounds_hold_at_the_isabel_grid():
    """At 100x500x500 the index arithmetic stays inside its bounds, read
    from shapes alone (nothing is allocated): RP's point count under
    2**29, the worst payload's bit offset under 2**31, and the dequant's
    2**24 guard set by a block's deltas, not by the point count."""
    n = int(np.prod(ISABEL))
    vol = jax.ShapeDtypeStruct(ISABEL, jnp.float32)
    main, rank, labels2b, n_cp, _, _ = jax.eval_shape(
        lambda f: _measure_one(f, 1e-3, block=DEFAULT_BLOCK, backend="jnp"),
        vol)
    nblocks = main[0].shape[0]
    assert nblocks == -(-n // DEFAULT_BLOCK) and n < 2 ** 29
    comp = jax.eval_shape(
        lambda *a: _pack_streams(*a, block=DEFAULT_BLOCK, mw_main=32,
                                 mw_rank=32, backend="jnp"),
        main, rank, labels2b, n_cp)
    cap = _worst_payload_cap(nblocks, DEFAULT_BLOCK)
    assert comp.szp.payload.shape[0] <= cap and 8 * cap < 2 ** 31
    assert n * 32 < 2 ** 31
    w = tri_guard_width(DEFAULT_BLOCK)
    assert (DEFAULT_BLOCK - 1) * (2 ** (w - 1) - 1) < 2 ** 24
    assert (DEFAULT_BLOCK - 1) * (2 ** w - 1) >= 2 ** 24
    # a grid of 2**29 points is refused by RP while tracing
    with pytest.raises(ValueError):
        jax.eval_shape(lambda f: _measure_one(f, 1e-3, block=DEFAULT_BLOCK,
                                              backend="jnp"),
                       jax.ShapeDtypeStruct((512, 1024, 1024), jnp.float32))
