"""From a profiler trace to the serving path's own spans.

The program marks the phases of its serving path with
``jax.profiler.TraceAnnotation`` spans named ``serving.*`` (see
``serving/frontend.py``).  Over the benchmark's window (``trace.WINDOW_SPAN``;
a span counts if it starts inside it):

- per span name: how many and their summed seconds (a name is read up to
  any ``#``, where metadata may follow it);
- the host lines (threads) that carry ``serving.launch``, and the part of
  those launches' time that the other ``serving.*`` spans inside them
  cover (their union, so nested spans count once);
- per device: its idle seconds (the gaps between ``trace.union`` of its op
  intervals) and the part of them inside a ``serving.launch`` span: the
  host busy with a launch rather than waiting for a request.

A trace with no ``serving.*`` span (a program that has none) reduces to
empty counts, no launch line and no idle time inside a launch.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

from . import trace

PREFIX = "serving."
LAUNCH = "serving.launch"


def base_name(name: str) -> str:
    return name.split("#", 1)[0]


def overlap(a: Sequence[trace.Interval], b: Sequence[trace.Interval]
            ) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def covered(launches: Sequence[trace.Interval],
            others: Sequence[trace.Interval]) -> float:
    """Summed length of each launch covered by the union of the ``others``
    that lie wholly inside it (launches on one line do not overlap)."""
    others = sorted(others)
    total, j = 0.0, 0
    for s, e in sorted(launches):
        while j < len(others) and others[j][0] < s:
            j += 1
        inside = []
        while j < len(others) and others[j][0] < e:
            if others[j][1] <= e:
                inside.append(others[j])
            j += 1
        total += sum(b - a for a, b in trace.union(inside, s, e))
    return total


def reduce(t: trace.Trace, devices: Optional[Sequence[int]] = None) -> dict:
    """Counts and seconds per ``serving.*`` name, the launch lines and
    their coverage, and per device the idle seconds and those inside a
    launch (``devices`` as in ``trace.reduce``; by default every TPU
    plane, none on a host without one)."""
    lo, hi = t.window()
    found: dict = {}
    launch_lines: List[int] = []
    launches: List[trace.Interval] = []
    covered_s = 0.0
    for i, line in enumerate(t.host):
        mine, others = [], []
        for e in line:
            name = base_name(e.name)
            if not name.startswith(PREFIX) or not lo <= e.start < hi:
                continue
            n_s = found.setdefault(name, [0, 0.0])
            n_s[0] += 1
            n_s[1] += (e.end - e.start) * 1e-9
            (mine if name == LAUNCH else others).append((e.start, e.end))
        if mine:
            launch_lines.append(i)
            launches += mine
            covered_s += covered(mine, others) * 1e-9
    in_launch = trace.union(launches, lo, hi)
    ids = sorted(t.devices) if devices is None else list(devices)
    idle_s, idle_in_launch_s = [], []
    for d in ids:
        busy = trace.union([(e.start, e.end) for e in t.devices.get(d, [])],
                           lo, hi)
        idle = trace.gaps(busy, lo, hi)
        idle_s.append(sum(e - s for s, e in idle) * 1e-9)
        idle_in_launch_s.append(overlap(idle, in_launch) * 1e-9)
    return {
        "spans": {k: {"n": n, "s": s} for k, (n, s) in sorted(found.items())},
        "launch_lines": launch_lines,
        "launch_covered_s": covered_s,
        "devices": ids,
        "idle_s": idle_s,
        "idle_in_launch_s": idle_in_launch_s,
    }


def mean_ms(reduced: dict, name: str) -> Optional[float]:
    """Mean duration (ms) of the spans named ``name``; None if none."""
    got = reduced["spans"].get(name)
    return got["s"] / got["n"] * 1e3 if got else None
