"""Least time over kernel time, in %: the least time is the larger of the
window's operations over the chip's peak and its bytes over HBM bandwidth,
counted by the model family from the true shapes (``models/<family>.ops``
and ``bytes_moved``) against the chip's peaks (``harness.costs``); kernel
time is the summed device time of the Pallas kernels in the traced
window."""
import sys

from harness import costs


def read(r):
    if r.trace is None or not r.window.get("kernel_calls"):
        return None
    kernel_s = sum(r.trace["kernel_s"])
    if kernel_s <= 0:
        return None
    calls, rows = r.window["kernel_calls"], r.window["kernel_rows"]
    n_ops = r.family.ops(r.shapes, rows)
    n_bytes = calls * r.family.bytes_moved(r.shapes, rows // calls)
    share, bound = costs.roofline(n_ops, n_bytes, kernel_s, r.device_kind,
                                  r.act_dtype)
    print(f"kernel_roofline: {calls} calls, {n_ops:.6g} ops, {n_bytes:.6g} "
          f"bytes, kernel {kernel_s:.6f} s, bound by {bound}",
          file=sys.stderr)
    return share
