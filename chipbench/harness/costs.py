"""Operations and bytes of one 4-bit MLP call, and the chip's peaks.

Counted from the configuration's true layer shapes, whatever schedule,
padding or fallback the program ran: a kernel that pads K from 784 to 896
does more work than the model needs, and the roofline share shows it.

    ops(rows)   = 2 * rows * sum_l K_l * N_l
    bytes(rows) = rows * K_0 * input bytes        (the batch read once)
                + rows * N_L * 4                  (f32 logits written once)
                + sum_l K_l * N_l / 2             (packed 4-bit codes)
                + sum_l (8 * N_l + 20)            (alpha1, bias, omega, scale)
"""
from __future__ import annotations

import json
import os
from typing import List, Sequence, Tuple

from .loader import BENCH

Shape = Tuple[int, int]


def layer_shapes(config: dict) -> List[Shape]:
    widths = [int(config["d_in"])] + [int(n) for n in config["features"]]
    return list(zip(widths[:-1], widths[1:]))


def ops(shapes: Sequence[Shape], rows: int) -> float:
    return 2.0 * rows * sum(k * n for k, n in shapes)


def bytes_moved(shapes: Sequence[Shape], rows: int,
                input_bytes: int = 4) -> float:
    k0, n_last = shapes[0][0], shapes[-1][1]
    weights = sum(k * n / 2 for k, n in shapes)
    epilogue = sum(8 * n + 20 for _, n in shapes)
    return float(rows * k0 * input_bytes + rows * n_last * 4 + weights
                 + epilogue)


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


def mfu(rows_per_s: float, shapes: Sequence[Shape], chips: int,
        device_kind: str, act_dtype: str) -> float:
    """Model FLOP/s over the peak of the chips used, in %."""
    return 100.0 * rows_per_s * ops(shapes, 1) / (
        chips * peak_ops(device_kind, act_dtype))
