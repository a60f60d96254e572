"""The chip's peaks, and the roofline and MFU arithmetic.

A model's operations and bytes are counted by its family
(``models/<family>.py``: ``ops(shapes, rows)``, ``bytes_moved(shapes,
rows, input_bytes)``), from the configuration's true shapes whatever
schedule, padding or fallback the program ran.
"""
from __future__ import annotations

import json
import os
from typing import Tuple

from .loader import BENCH


def peaks(device_kind: str, path: str = None) -> dict:
    with open(path or os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


def peak_ops(device_kind: str, act_dtype: str) -> float:
    p = peaks(device_kind)
    return p["int8_ops_per_s"] if act_dtype == "int8" \
        else p["bfloat16_ops_per_s"]


def roofline(n_ops: float, n_bytes: float, seconds: float,
             device_kind: str, act_dtype: str) -> Tuple[float, str]:
    """(least time / ``seconds`` in %, the bound that sets the least time)."""
    t_ops = n_ops / peak_ops(device_kind, act_dtype)
    t_mem = n_bytes / peaks(device_kind)["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "hbm"
    return 100.0 * max(t_ops, t_mem) / seconds, bound


def mfu(rows_per_s: float, ops_per_row: float, chips: int,
        device_kind: str, act_dtype: str) -> float:
    """Model FLOP/s over the peak of the chips used, in %."""
    return 100.0 * rows_per_s * ops_per_row / (
        chips * peak_ops(device_kind, act_dtype))
