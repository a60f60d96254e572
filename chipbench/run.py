#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Prints one JSON line (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and ``checks`` last).
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  See ``harness/bench.py``.
"""
import os
import sys
import time


def process_start() -> float:
    """When this process started, on the ``perf_counter`` clock (Linux's
    /proc; elsewhere, now)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.perf_counter() - (uptime
                                      - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


T_START = process_start()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

from harness.bench import execute  # noqa: E402

if __name__ == "__main__":
    sys.exit(execute(t_start=T_START))
