#!/usr/bin/env python3
"""Read a knee sweep's lines (``run.py --sweep``) and print the knee: the
highest offered rate whose window kept up (nothing failed, at most 1% of
its requests still queued at the close, completed/offered >= 0.99) with
its p99 within ``--limit-ms``, the latency tier's 25 ms deadline.

The p99 and not the p95: a 4 s window at thousands of requests per
second has hundreds of requests beyond its p99, and on one v5e the p95
stayed under 25 ms at 12,000 requests/s while host stalls had already
pushed the p99 past 100 ms, and runs at 0.8 x that rate fell behind.

    python chipbench/tools/knee.py <sweep output> [--limit-ms 25]
"""
import argparse
import json
import math
import sys


def knee(lines, limit_ms: float) -> float:
    best = 0.0
    for s in lines:
        ok = (s["failed"] == 0
              and s["queued_at_close"] <= 0.01 * s["attempted"]
              and s["completed_over_offered"] >= 0.99
              and math.isfinite(s["p99_ms"]) and s["p99_ms"] <= limit_ms)
        if ok:
            best = max(best, s["offered_per_s"])
    return best


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("sweep")
    ap.add_argument("--limit-ms", type=float, default=25.0)
    args = ap.parse_args()
    with open(args.sweep) as f:
        lines = [json.loads(line) for line in f if line.startswith("{")]
    print(knee(lines, args.limit_ms))
    return 0


if __name__ == "__main__":
    sys.exit(main())
