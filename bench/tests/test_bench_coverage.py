"""The readers of nested stage scopes, of trace coverage and of the
program's width-bucket counters."""
import pytest

from bench import devtrace, harness, spec
from bench.devtrace import Op, Reduction, Span
from bench.tests.test_bench_faults import _cell
from bench.tests.test_bench_trace import _red

RP = ("jit(_compress_measure_batch)/vmap(toposzp.stage_detect)/"
      "toposzp.stage_rp/gather:")
MD = ("jit(_decompress_batch)/cond/branch_0_fun/while/body/closed_call/"
      "toposzp.stage_decode/toposzp.stage_decode_md/gather:")


class _Cell:
    def __init__(self, operation, compressor="toposzp"):
        self.config = {"grid": [10, 20], "fields_per_call": 4,
                       "compressor": compressor}
        self.traffic = {"operation": operation}


def _ctx(red, operation, compressor="toposzp"):
    return devtrace.Context(red, _Cell(operation, compressor), "TPU v5 lite")


def _read(name, ctx):
    return spec.reader(name)(ctx)


@pytest.mark.parametrize("scope,name,hit", [
    (RP, "toposzp.stage_detect", True),
    (RP, "toposzp.stage_rp", True),
    ("jit(f)/vmap(toposzp.stage_detect)/vmap(toposzp.stage_rp)/sort:",
     "toposzp.stage_rp", True),
    (MD, "toposzp.stage_decode", True),
    (MD, "toposzp.stage_decode_md", True),
    ("jit(f)/toposzp.stage_decode/gather:", "toposzp.stage_decode_md",
     False),
    ("jit(f)/vmap(toposzp.stage_detect)/sort:", "toposzp.stage_rp", False),
])
def test_nested_stage_scope_matching(scope, name, hit):
    assert devtrace.in_scope(scope, [name]) is hit


def test_an_op_under_a_nested_scope_counts_for_both_metrics():
    # one call, one field per call: ms per field is the op's length
    spans = [[Span(0, 100, "bench.call")]]
    red = Reduction([Op(0, 30, "fusion.8", RP, 0),
                     Op(30, 50, "sort.8", RP.replace("toposzp.stage_rp/",
                                                     ""), 0),
                     Op(50, 60, "gather.1", MD, 0),
                     Op(60, 90, "fusion.1", MD.replace(
                         "toposzp.stage_decode_md/", ""), 0)], spans)
    ctx = _ctx(red, "compress")
    per_ns = 1000.0 / 1e9 / ctx.fields
    assert _read("detect_ms", ctx) == pytest.approx(50 * per_ns)
    assert _read("rp_ms", ctx) == pytest.approx(30 * per_ns)
    assert _read("decode_ms", ctx) == pytest.approx(40 * per_ns)
    assert _read("decode_md_ms", ctx) == pytest.approx(10 * per_ns)


def test_unscoped_share_of_the_busy_time():
    # _red(): busy 73 ns; detect ops cover 5-30 and 62-90, szp.stage_pack
    # 95-100; the while op's 0-5 and 30-40 are under no stage
    red = _red()
    assert _read("unscoped.compress", _ctx(red, "compress")) == \
        pytest.approx(100 * (1 - 58 / 73))
    assert _read("unscoped.compress", _ctx(red, "decompress")) is None
    assert _read("unscoped.decompress", _ctx(red, "compress")) is None
    assert _read("unscoped.decompress", _ctx(red, "decompress")) == \
        pytest.approx(100.0)
    red = Reduction(red.ops + [Op(0, 100, "fusion.7", MD, 0)],
                    [[Span(0, 100, "bench.call")]])
    assert _read("unscoped.decompress", _ctx(red, "decompress")) == \
        pytest.approx(0.0)


def test_unscoped_share_is_none_without_device_ops():
    red = Reduction([], [[Span(0, 100, "bench.call")]])
    assert _read("unscoped.compress", _ctx(red, "compress")) is None
    assert _read("unscoped.decompress", _ctx(red, "decompress")) is None


def test_pack_width_bits_from_counters():
    ctx = _ctx(_red(), "compress")
    ctx.counters = {"toposzp.compress.calls": 48.0,
                    "toposzp.compress.bucket_8": 32.0,
                    "toposzp.compress.bucket_16": 16.0,
                    "szp.compress.bucket_32": 16.0,
                    "toposzp.decompress.calls": 16.0}
    assert _read("pack_width_bits", ctx) == pytest.approx(32 / 3)
    szp = _ctx(_red(), "compress", compressor="szp")
    szp.counters = ctx.counters
    assert _read("pack_width_bits", szp) == pytest.approx(32.0)
    ctx.counters = {"toposzp.compress.calls": 16.0,
                    "toposzp.decompress.calls": 16.0}
    assert _read("pack_width_bits", ctx) is None
    ctx.counters = {}
    assert _read("pack_width_bits", ctx) is None


@pytest.fixture
def obs_isolation():
    from repro import obs
    was = obs.enabled()
    obs.reset()
    yield
    obs.set_enabled(was)
    obs.reset()


@pytest.mark.parametrize("name", ["atm_topo.compress", "atm_szp.compress"])
def test_traced_run_reports_the_window_width_bucket(name, cpu_chip, no_cache,
                                                    obs_isolation, capsys):
    from repro.core import bitpack
    r = harness.run_cell(_cell(name), 2**33 + 17, 0.05, True, 0.0,
                         check_chip=cpu_chip)
    err = capsys.readouterr().err
    assert r["correct"] is True
    assert r["metrics"]["pack_width_bits"]["unit"] == "bits"
    assert r["metrics"]["pack_width_bits"]["value"] in bitpack.WIDTH_BUCKETS
    assert "pack_width_bits found nothing" not in err
    # the CPU trace holds no TPU op: the device readers stay silent
    assert "unscoped.compress" not in r["metrics"]
