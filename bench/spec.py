"""Finds a cell's configuration, traffic mix and metric readers by name.

Everything a cell needs is data named in ``BENCHMARK.json`` or a file of
its own under this directory, so a new cell, configuration, traffic mix or
per-layer metric is added by adding files and entries, never by editing
one:

* configuration: the ``file`` of its ``configs`` entry (sizes, error
  bound, compressor and the guarantees ``correct`` holds it to);
* traffic mix: ``traffic/<traffic>.json`` (operation and field mix);
* per-layer metric: ``metrics/<name>.py``, whose ``read(ctx)`` returns
  the number or ``None`` when the trace holds nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
from typing import NamedTuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list     # BENCHMARK.json entries this cell reports
    per_layer: list


def grid_points(config: dict) -> int:
    """Points of one field of the configuration's grid, whatever its rank:
    (ny, nx) or (nz, ny, nx)."""
    return math.prod(int(s) for s in config["grid"])


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _reports(metric: dict, cell: str, e2e_names=None) -> bool:
    """A metric with ``workloads`` is reported in those cells; without,
    an end-to-end metric is reported in every cell and a per-layer metric
    in every cell that reports the end-to-end metric it ``moves``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def find_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as fh:
        traffic = json.load(fh)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def reader(metric_name: str, root: str = ROOT):
    """``read`` of ``bench/metrics/<metric_name>.py``."""
    path = os.path.join(root, "bench", "metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
