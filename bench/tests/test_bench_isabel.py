"""The Hurricane-ISABEL cell: it resolves with its metrics, its CD readers
read a trace, the program's 3-D labels and outputs meet the benchmark's
own reference, and a run of the cell on the CPU at a small volume is
``correct`` and is not under the cell's faults."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import devtrace, harness, reference, spec
from bench.devtrace import Op, Reduction, Span
from bench.target import Target

CELL = "isabel_topo.compress"
SMALL = {"grid": [6, 20, 24], "fields_per_call": 3}
CD = ("jit(_compress_measure_batch)/vmap(toposzp.stage_detect)/"
      "toposzp.stage_cd/pallas_call:")


def test_cell_resolves_with_its_metrics():
    cell = spec.find_cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["compressor"] == "toposzp"
    assert cfg["grid"] == [100, 500, 500] and cfg["dtype"] == "float32"
    assert cfg["eb"] == 0.001 and cfg["reduced"] == []
    assert spec.grid_points(cfg) == 25_000_000
    assert {"err_bound_eb": 2, "fp": 0, "ft": 0}.items() <= \
        cfg["guarantees"].items()
    assert cell.traffic == spec.find_cell("atm_topo.compress").traffic
    assert [m["name"] for m in cell.end_to_end] == ["compress_GBps", "ratio",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "idle.compress", "detect_ms", "rp_ms", "quant_ms", "pack_ms",
        "compact_ms", "detect_roofline", "unscoped.compress",
        "pack_width_bits", "cd_ms", "cd_roofline"}
    atm = spec.find_cell("atm_topo.compress")
    assert {"cd_ms", "cd_roofline"} <= {m["name"] for m in atm.per_layer}


class _Cell:
    config = {"grid": [100, 500, 500], "fields_per_call": 4,
              "compressor": "toposzp"}
    traffic = {"operation": "compress"}


def test_cd_readers_read_the_nested_scope():
    # one call of 4 fields: CD 2 ms of the 10 ms under stage_detect
    spans = [[Span(0, 20e6, "bench.call")]]
    red = Reduction([Op(0, 2e6, "cp_detect3", CD, 0),
                     Op(2e6, 10e6, "sort.1",
                        CD.replace("toposzp.stage_cd/pallas_call:",
                                   "toposzp.stage_rp/sort:"), 0)], spans)
    ctx = devtrace.Context(red, _Cell, "TPU v5 lite")
    assert devtrace.in_scope(CD, ["toposzp.stage_cd"])
    assert spec.reader("cd_ms")(ctx) == pytest.approx(0.5)
    assert spec.reader("detect_ms")(ctx) == pytest.approx(2.5)
    share = spec.reader("cd_roofline")(ctx)
    # 4.25 B/point over 25.0M points in 0.5 ms at 819 GB/s
    assert share == pytest.approx(100 * 4.25 * 25e6 / 819e9 / 0.5e-3)
    assert 0 < share < 100
    # a program without the scope (the parent's) gives nothing to read
    bare = Reduction([Op(0, 2e6, "cp", CD.replace("toposzp.stage_cd/", ""),
                         0)], spans)
    ctx = devtrace.Context(bare, _Cell, "TPU v5 lite")
    assert spec.reader("cd_ms")(ctx) is None
    assert spec.reader("cd_roofline")(ctx) is None


def _volumes():
    rng = np.random.default_rng(16)
    yield rng.random((8, 12, 16), dtype=np.float32)
    yield np.round(rng.random((6, 6, 6)) * 3).astype(np.float32)    # ties
    yield np.round(rng.random((4, 5, 3)) * 2).astype(np.float32)    # ties
    yield rng.random((1, 7, 8), dtype=np.float32)                   # faces
    yield rng.random((6, 1, 5), dtype=np.float32)
    yield rng.random((5, 6, 1), dtype=np.float32)
    yield rng.random((2, 2, 9), dtype=np.float32)                   # edges
    yield rng.random((1, 1, 1), dtype=np.float32)                   # corner
    yield np.zeros((3, 4, 5), np.float32)
    x = np.linspace(-1, 1, 9, dtype=np.float32)
    yield (x[:, None, None] ** 2 + x[None, :, None] ** 2
           - x[None, None, :] ** 2)                                   # saddle


@pytest.mark.parametrize("backend", ["interpret", "jnp"])
@pytest.mark.parametrize("i", range(10))
def test_program_3d_labels_equal_the_reference(i, backend):
    from repro.kernels import ops
    f = jnp.asarray(list(_volumes())[i])
    np.testing.assert_array_equal(
        np.asarray(ops.cp_detect(f, backend=backend)),
        np.asarray(reference.classify(f)))


def _fields(seed, shape=(8, 24, 32), n=3):
    from bench.fields import make_fields
    return make_fields(seed, n, shape, spec.find_cell(CELL).traffic["fields"])


def test_readings_of_the_normal_path_on_volumes():
    from repro.core.szp import szp_compress_batch, szp_decompress_batch
    cfg = spec.find_cell(CELL).config
    eb, shape = cfg["eb"], (8, 24, 32)
    fields = _fields(2**33 + 29)
    t = Target("toposzp", shape, eb)
    rec, _ = harness.reconstruction(t, "compress", t.compress(fields))
    r = reference.readings(fields, rec, eb, cfg["guarantees"])
    assert r["fp"]["value"] == 0 and r["ft"]["value"] == 0
    assert r["max_err"]["value"] <= r["max_err"]["limit"] < 2 * eb * 1.001
    plain = szp_decompress_batch(szp_compress_batch(fields, eb), shape, eb)
    rp = reference.readings(fields, plain, eb, cfg["guarantees"])
    fn = sum(p["fn"] for p in r["fields"])
    assert fn <= sum(p["fn"] for p in rp["fields"])
    assert sum(p["fn_plain"] for p in r["fields"]) > 0


def _run(cpu_chip, seed=2**33 + 31):
    cell = spec.find_cell(CELL)
    cell = cell._replace(config=dict(cell.config, **SMALL))
    return harness.run_cell(cell, seed, 0.05, False, 0.0,
                            check_chip=cpu_chip)


def test_a_volume_cell_run_is_correct(cpu_chip, no_cache):
    r = _run(cpu_chip)
    assert r["correct"] is True and r["failed"] == 0
    assert r["metrics"]["compress_GBps"]["value"] > 0
    assert r["metrics"]["ratio"]["value"] > 1
    assert set(r["checks"]) == {"max_err", "fp", "ft", "fn_share"}


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.mark.parametrize("fault", ["altered", "restore_skipped", "control"])
def test_a_volume_cell_run_is_not_correct_under_its_faults(
        fault, cpu_chip, no_cache, monkeypatch):
    from bench.tests.test_bench_faults import _alter_stream
    if fault == "altered":
        real = Target.compress
        monkeypatch.setattr(Target, "compress",
                            lambda self, f: _alter_stream(real(self, f)))
    elif fault == "control":
        real = Target.compress
        monkeypatch.setattr(Target, "compress",
                            lambda self, f: real(self, _bf16(f)))
    else:
        from repro.core.szp import szp_decompress_batch
        monkeypatch.setattr(
            Target, "decompress", lambda self, comp: szp_decompress_batch(
                comp.szp, self.shape, self.eb))
    r = _run(cpu_chip)
    assert r["correct"] is False and r["failed"] > 0
