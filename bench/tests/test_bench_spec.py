"""Discovery of configurations, cells and metric readers by name, and the
shape of BENCHMARK.json."""
import json
import os
import re
import shutil

import pytest

from bench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(w):
    cell = spec.find_cell(w)
    assert cell.config["grid"] and cell.config["fields_per_call"] > 0
    assert cell.traffic["operation"] in ("compress", "decompress")
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.reader(m["name"]))
        assert m["moves"] in [e["name"] for e in cell.end_to_end]


def test_names_units_and_files_follow_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
    layers = {m["layer"] for m in BENCH["per_layer"]}
    perf = open(os.path.join(spec.ROOT, "PERF.md")).read()
    assert all(layer in perf for layer in layers)


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.find_cell("no_such.cell")


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path):
    """A new configuration (here a 3-D grid), traffic mix, cell and reader
    need new files and entries only."""
    root = tmp_path / "co"
    shutil.copytree(os.path.join(spec.ROOT, "bench"), root / "bench")
    bench = json.loads(json.dumps(BENCH))
    cfg = dict(spec.find_cell("atm_szp.decompress").config,
               name="vol_szp", grid=[100, 500, 500], fields_per_call=4)
    (root / "bench" / "configs" / "vol_szp.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "decompress_grf.json").write_text(
        json.dumps({"operation": "decompress", "loop": "closed",
                    "clients": 1, "fields": [{"generator": "grf",
                                              "power": 3.0}]}))
    bench["configs"].append({"name": "vol_szp", "source": "x",
                             "file": "bench/configs/vol_szp.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "vol_szp.decompress_grf",
                               "config": "vol_szp",
                               "traffic": "decompress_grf",
                               "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "decompress_GBps":
            m["workloads"].append("vol_szp.decompress_grf")
    bench["per_layer"].append({"name": "new_ms", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "x", "moves": "decompress_GBps"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "metrics" / "new_ms.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    cell = spec.find_cell("vol_szp.decompress_grf", str(root))
    assert cell.config["compressor"] == "szp"
    assert spec.grid_points(cell.config) == 25_000_000
    assert cell.traffic["operation"] == "decompress"
    # no workloads key: reported wherever the metric it moves is
    assert [m["name"] for m in cell.per_layer] == ["new_ms"]
    assert [m["name"] for m in cell.end_to_end] == ["decompress_GBps",
                                                    "setup_s"]
    assert spec.reader("new_ms", str(root))(None) == 1.5
