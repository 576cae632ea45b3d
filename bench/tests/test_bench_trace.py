"""The trace reduction on hand-built events and a hand-encoded xplane."""
import math

import pytest

from bench import devtrace, harness
from bench.devtrace import Op, Reduction, Span

STAGE = "jit(_compress_measure_batch)/vmap(toposzp.stage_detect)/sort:"


def test_union_of_nested_and_overlapping_intervals():
    assert devtrace.union_ns([(0, 10), (2, 5), (8, 12), (20, 25)]) == 17
    assert devtrace.union_ns([]) == 0
    assert devtrace.merged([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]


@pytest.mark.parametrize("scope,name,hit", [
    (STAGE, "toposzp.stage_detect", True),
    ("jit(f)/cond/branch_0_fun/while/body/closed_call/"
     "toposzp.stage_restore/jit(extrema_restore)/slice:",
     "toposzp.stage_restore", True),
    ("jit(f)/transpose(jvp(vmap(szp.stage_quant)))/add:", "szp.stage_quant",
     True),
    ("jit(f)/vmap(jit(compact_local_blocks))/pallas_call:",
     "compact_local_blocks", True),
    (STAGE, "szp.stage_detect", False),            # no partial-name match
    (STAGE, "stage_detect", False),
    ("", "toposzp.stage_detect", False),
])
def test_scope_matching_under_nested_names(scope, name, hit):
    assert devtrace.in_scope(scope, [name]) is hit


def _red():
    # two timed calls on one chip; a while op (0-40) nests two detect ops
    spans = [[Span(0, 55, "bench.call"), Span(1, 20, "compress.quant"),
              Span(41, 54, "compress.pack"), Span(58, 100, "bench.call")]]
    ops = [Op(0, 40, "while.1", "jit(f)/while", 0),
           Op(5, 15, "sort.1", STAGE, 0),
           Op(15, 30, "fusion.2", STAGE.replace("sort", "gather"), 0),
           Op(62, 90, "fusion.2", STAGE.replace("sort", "gather"), 0),
           Op(95, 130, "fusion.9", "jit(g)/szp.stage_pack/x:", 0),
           Op(200, 300, "late", STAGE, 0)]                # past the window
    return Reduction(ops, spans)


def test_window_busy_and_scope_time():
    red = _red()
    assert red.calls == 2
    assert red.window_s == pytest.approx(100e-9)
    # busy: 0-40, 62-90, 95-100 (clipped at the window's end)
    assert red.busy_s == pytest.approx(73e-9)
    assert red.scope_s("toposzp.stage_detect") == pytest.approx(53e-9)
    assert red.scope_s("szp.stage_pack") == pytest.approx(5e-9)
    assert red.scope_s("toposzp.stage_restore") == 0


def test_per_field_division_and_idle_share():
    red = _red()

    class Cell:
        config = {"grid": [10, 20], "fields_per_call": 4,
                  "compressor": "toposzp"}
        traffic = {"operation": "compress"}
    ctx = devtrace.Context(red, Cell, "TPU v5 lite")
    assert ctx.fields == 8
    assert ctx.n_points == 200
    assert ctx.ms_per_field("toposzp.stage_detect") == pytest.approx(
        53e-9 * 1000 / 8)
    assert ctx.ms_per_field("toposzp.stage_restore") is None
    assert ctx.idle_pct() == pytest.approx(27.0)


@pytest.mark.parametrize("grid", [[1800, 3600], [100, 500, 500], [7, 9]])
def test_points_and_raw_bytes_take_every_axis_of_the_grid(grid):
    class Cell:
        config = {"grid": grid, "fields_per_call": 4,
                  "compressor": "toposzp"}
        traffic = {"operation": "compress"}
    ctx = devtrace.Context(_red(), Cell, "TPU v5 lite")
    assert ctx.n_points == math.prod(grid)
    assert harness.raw_bytes(Cell.config) == 4 * math.prod(grid) * 4
    if grid == [1800, 3600]:           # the 2-D cells read what they did
        assert ctx.n_points == 6_480_000
        assert harness.raw_bytes(Cell.config) == 103_680_000
    if grid == [100, 500, 500]:
        assert ctx.n_points == 25_000_000
        assert harness.raw_bytes(Cell.config) == 400_000_000


def test_self_times_leave_out_nested_time():
    st = _red().self_times()
    assert st["while.1 @ while"] == pytest.approx(15e-9)   # 40 - 25 nested
    assert st["sort.1 @ vmap(toposzp.stage_detect)/sort:"] == \
        pytest.approx(10e-9)
    gather = "fusion.2 @ vmap(toposzp.stage_detect)/gather:"
    assert st[gather] == pytest.approx(43e-9)
    assert _red().top_ops(1)[0][0] == gather


def test_idle_gaps_named_by_host_spans():
    gaps = _red().idle_gaps(10)
    assert [g[0] for g in gaps] == ["bench.call > compress.pack",
                                    "bench.call"]
    assert [g[1] for g in gaps] == pytest.approx([22e-9, 5e-9])


def test_no_call_span_is_an_error():
    with pytest.raises(ValueError):
        Reduction([], [[Span(0, 1, "x")]])


# -- a hand-encoded XSpace ---------------------------------------------------

def _varint(v):
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _f(num, val):
    if isinstance(val, int):
        return _varint(num << 3) + _varint(val)
    if isinstance(val, str):
        val = val.encode()
    return _varint(num << 3 | 2) + _varint(len(val)) + val


def _plane(name, lines, ev_meta, stat_meta):
    body = _f(2, name)
    for ln in lines:
        body += _f(3, ln)
    for mid, md in ev_meta.items():
        body += _f(4, _f(1, mid) + _f(2, md))
    for sid, sname in stat_meta.items():
        body += _f(5, _f(1, sid) + _f(2, _f(1, sid) + _f(2, sname)))
    return _f(1, body)


def _line(name, ts, events):
    body = _f(2, name) + _f(3, ts)
    for mid, off_ps, dur_ps in events:
        body += _f(4, _f(1, mid) + _f(2, off_ps) + _f(3, dur_ps))
    return body


def test_read_xspace_decodes_ops_scopes_and_host_spans(tmp_path):
    tf_op = _f(1, 7) + _f(5, STAGE)                       # XStat
    device = _plane(
        "/device:TPU:0",
        [_line("XLA Modules", 1000, [(1, 0, 9_000_000)]),
         _line("XLA Ops", 1000, [(1, 0, 4_000_000), (2, 4_000_000, 1000)])],
        {1: _f(1, 1) + _f(2, "%fusion.3 = f32[8] fusion(...)")
         + _f(4, "fusion.3") + _f(5, tf_op),
         2: _f(1, 2) + _f(2, "%copy.1 = f32[8] copy(...)")},
        {7: "tf_op"})
    host = _plane("/host:CPU",
                  [_line("python", 900, [(5, 0, 10_000_000)])],
                  {5: _f(1, 5) + _f(2, "bench.call")}, {})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(device + host)
    ops, threads = devtrace.read_xspace(str(path))
    assert ops == [Op(1000.0, 5000.0, "fusion.3", STAGE, 0),
                   Op(5000.0, 5001.0, "%copy.1 = f32[8] copy(...)", "", 0)]
    assert threads == [[Span(900.0, 10900.0, "bench.call")]]
    red = Reduction(ops, threads)
    assert red.scope_s("toposzp.stage_detect") == pytest.approx(4e-6)
