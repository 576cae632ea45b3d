"""Shared benchmark helpers: timing, CSV/JSON emission, dataset sizing."""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Union

import jax
import numpy as np

from repro.utils import enable_compile_cache

enable_compile_cache()

# benchmark-scale knob: FULL=1 uses the paper's grid sizes (ATM 1800x3600);
# default runs reduced grids so the suite finishes quickly on 1 CPU core.
FULL = os.environ.get("REPRO_BENCH_FULL", "0") == "1"

REDUCED = {
    "ATM": (450, 900),
    "CLIMATE": (384, 576),
    "ICE": (384, 320),
    "LAND": (192, 288),
    "OCEAN": (384, 320),
}


def bench_grid(name: str):
    from repro.data.fields import DATASETS
    return DATASETS[name] if FULL else REDUCED[name]


def timeit(fn, *args, warmup: int = 1, iters: int = 3, **kw):
    """Median wall time of a blocking call (jit warm)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


# JSON results schema (benchmarks/check_regression.py consumes this):
#   {"schema_version": 1, "records": [
#       {"name": str, "us_per_call": float, "metrics": {str: float|int|str}}]}
SCHEMA_VERSION = 1

_RECORDS: List[Dict] = []

Metrics = Union[str, Dict[str, object]]


def reset_records() -> None:
    _RECORDS.clear()


def records() -> List[Dict]:
    return list(_RECORDS)


def emit(name: str, us_per_call: float, derived: Metrics = ""):
    """Record one benchmark row and print the legacy CSV line.

    ``derived`` may be a pre-formatted ``k=v;...`` string (legacy) or a
    dict of metrics; dicts are what the JSON results file and the
    regression gate consume.
    """
    if isinstance(derived, dict):
        metrics = derived
        text = ";".join(f"{k}={_fmt(v)}" for k, v in derived.items())
    else:
        metrics = {"derived": derived} if derived else {}
        text = derived
    _RECORDS.append({"name": name, "us_per_call": float(us_per_call),
                     "metrics": metrics})
    print(f"{name},{us_per_call:.1f},{text}")


def _fmt(v) -> str:
    return f"{v:.4g}" if isinstance(v, float) else str(v)


def write_json(path: str, bench: str, smoke: Optional[bool] = None) -> None:
    """Write the collected records as a machine-readable results file."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    doc = {"schema_version": SCHEMA_VERSION, "bench": bench,
           "records": records()}
    if smoke is not None:
        doc["smoke"] = smoke
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"[bench] wrote {path}")
