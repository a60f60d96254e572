"""From a profiler trace to device busy time, kernel time and a breakdown.

The window is the benchmark's own host span (``WINDOW_SPAN``, a
``jax.profiler.TraceAnnotation``); every device number is clipped to it.

- busy: the union of the intervals in which an operation ran on a device
  (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), per device;
- kernel time: the summed durations of the Pallas kernels' events, which
  the TPU runtime records as custom calls (``is_kernel``);
- op time: the seconds of every device operation, by name (``op_name``),
  summed over the devices;
- breakdown: the device operations that took most time, and the longest
  idle gaps on each device, named by the innermost host span that covers
  the gap's midpoint.

Run ``python chipbench/harness/trace.py <log dir or .xplane.pb>`` to print
a trace's planes, lines and most frequent events.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP = 10

Interval = Tuple[float, float]


class Event:
    __slots__ = ("name", "start", "end")

    def __init__(self, name: str, start: float, end: float):
        self.name, self.start, self.end = name, start, end


class Trace:
    """Device op events per device and host events, times in ns."""

    def __init__(self, devices: Dict[int, List[Event]],
                 host: List[List[Event]]):
        self.devices = devices
        self.host = host

    def window(self) -> Interval:
        spans = [e for line in self.host for e in line
                 if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        return min(e.start for e in spans), max(e.end for e in spans)


def find_xspace(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:                  # noqa: BLE001 — stats are optional
        return {}


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xspace(path))
    devices: Dict[int, List[Event]] = {}
    host: List[List[Event]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    start = float(ev.start_ns)
                    evs.append(Event(ev.name, start,
                                     start + float(ev.duration_ns)))
            devices[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.append([Event(ev.name, float(ev.start_ns),
                                   float(ev.start_ns) + float(ev.duration_ns))
                             for ev in line.events])
    return Trace(devices, host)


KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def is_kernel(ev: Event) -> bool:
    """A Pallas (Mosaic) kernel: the TPU runtime names each op event by its
    HLO text, and a kernel is a custom call to ``tpu_custom_call``."""
    return KERNEL_TARGET in ev.name


def op_name(name: str) -> str:
    """``%fantastic4_fused_mlp_pallas.1 = f32[...] custom-call(...)`` ->
    ``fantastic4_fused_mlp_pallas``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_label(trace: Trace, t: float) -> str:
    """The innermost host span (not the window's) that covers ``t``."""
    best = None
    for line in trace.host:
        for e in line:
            if e.start <= t <= e.end and e.name != WINDOW_SPAN and (
                    best is None or e.end - e.start < best.end - best.start):
                best = e
    return best.name if best is not None else "no host span"


def reduce(trace: Trace, devices: Optional[Sequence[int]] = None) -> dict:
    """Busy and kernel seconds per device over the window, the seconds of
    every op by name summed over the devices (``op_s``), and the breakdown
    (at most ``TOP`` entries in each list)."""
    lo, hi = trace.window()
    ids = sorted(trace.devices) if devices is None else list(devices)
    if not ids:
        raise ValueError("trace has no TPU device plane")
    busy_s, kernel_s, per_op = [], [], collections.Counter()
    all_gaps = []
    for d in ids:
        evs = trace.devices.get(d, [])
        merged = union([(e.start, e.end) for e in evs], lo, hi)
        busy_s.append(sum(e - s for s, e in merged) * 1e-9)
        k = 0.0
        for e in evs:
            dur = min(e.end, hi) - max(e.start, lo)
            if dur <= 0:
                continue
            per_op[op_name(e.name)] += dur * 1e-9
            if is_kernel(e):
                k += dur * 1e-9
        kernel_s.append(k)
        all_gaps += [(e - s, s, e, d) for s, e in gaps(merged, lo, hi)]
    all_gaps.sort(reverse=True)
    idle = [[f"TPU:{d} " + host_label(trace, (s + e) / 2), g * 1e-9]
            for g, s, e, d in all_gaps[:TOP]]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "kernel_s": kernel_s,
        "op_s": dict(per_op),
        "breakdown": {
            "device_ops": [[n, s] for n, s in per_op.most_common(TOP)],
            "idle_gaps": idle,
        },
    }


def describe(path: str) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xspace(path))
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  line {line.name!r}: {len(evs)} events")
            for name, n in names.most_common(8):
                ev = next(e for e in evs if e.name == name)
                print(f"    {n} x {name!r} {ev.duration_ns:.0f} ns "
                      f"stats {_stats(ev)}")


if __name__ == "__main__":
    describe(sys.argv[1])
