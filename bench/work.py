"""The work a stage must do, counted from shapes, and the chip's peaks.

A stage's roofline share is the least time the chip could take for the
stage's logical bytes at peak HBM bandwidth, over the stage's device time.
Logical bytes count what the stage must read and write whatever implements
it (inputs and outputs at their unpadded sizes, once each), not what the
current kernels move, so the share stays comparable when a kernel is
replaced and cannot pass 100% unless the time leaves out part of the work.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    with open(PEAKS_FILE) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}")
    return table[device_kind]


def detect_bytes(n_points: int) -> float:
    """CD + RP of one field: read the f32 field, write the 2-bit label
    map and the int32 rank of every point."""
    return 4.0 * n_points + n_points / 4.0 + 4.0 * n_points


def restore_bytes(n_points: int) -> float:
    """CP^ + RP^ + RS^ + FP/FT suppression of one field: read the f32
    dequantized field, the 2-bit label map and the int32 ranks, write the
    f32 restored field."""
    return 4.0 * n_points + n_points / 4.0 + 4.0 * n_points + 4.0 * n_points


def hbm_share(logical_bytes: float, seconds: float, device_kind: str) -> float:
    """Percent of peak HBM bandwidth: bytes / peak bytes/s / seconds."""
    if seconds <= 0:
        raise ValueError("a roofline share needs a positive device time")
    return 100.0 * logical_bytes / peaks(device_kind)["hbm_bytes_per_s"] \
        / seconds
