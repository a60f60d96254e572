#!/usr/bin/env python3
"""Run one cell traced, as ``run.py --trace 1`` does, and split its host
time by the program's ``serving.*`` spans.

    python chipbench/tools/span_split.py --workload <cell> --seed <n> \
        --seconds <s> [--out <dir>]

Prints ``run.py``'s own result line, then one JSON line: the cost of one
span on this host with no trace active (plain, and with the metadata a
launch sets); ``harness/spans.py``'s reduction of the window; the mean
milliseconds of each span, the launch's self time (its duration less the
union of its child spans) and the share the children cover; the share of
the device's idle time inside a launch; and, for open-loop traffic, the
micro-batcher's ``queue_wait_s`` and ``flushed_requests`` over the window
and its drain, with their mean in ms.  ``--out`` keeps the trace.
"""
import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

from harness import bench, openloop, spans, trace  # noqa: E402

COUNTERS = ("flushes", "flushed_requests", "queue_wait_s")


def span_cost_us(n: int = 200_000) -> dict:
    """Microseconds per span entered and left with no trace active."""
    from jax.profiler import TraceAnnotation
    t = time.perf_counter()
    for _ in range(n):
        with TraceAnnotation("serving.cost"):
            pass
    plain = time.perf_counter() - t
    t = time.perf_counter()
    for _ in range(n):
        with TraceAnnotation("serving.cost") as span:
            span.set_metadata(bucket=16, requests=12, rows=12)
    meta = time.perf_counter() - t
    return {"span_us": plain / n * 1e6, "span_metadata_us": meta / n * 1e6}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    cost = span_cost_us()
    kept = {}

    # keep the trace bench.py loads, and the batcher's counters around
    # the last (the measured) window
    load = trace.load

    def keep(path):
        kept["trace"] = load(path)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            shutil.copy(trace.find_xspace(path), args.out)
        return kept["trace"]

    window = openloop.OpenLoop.window

    def counted(self, *a, **k):
        st = self.batcher.stats
        before = {c: st.get(c, 0) for c in COUNTERS}
        w = window(self, *a, **k)
        kept["counters"] = {c: st.get(c, 0) - before[c] for c in COUNTERS}
        return w

    trace.load = keep
    openloop.OpenLoop.window = counted
    rc = bench.execute(["--workload", args.workload, "--seed",
                        str(args.seed), "--seconds", str(args.seconds),
                        "--trace", "1"])
    if rc or "trace" not in kept:
        return rc or 1
    r = spans.reduce(kept["trace"])
    out = {"workload": args.workload, "seed": args.seed, "cost": cost,
           "spans": r,
           "mean_ms": {k: spans.mean_ms(r, k) for k in r["spans"]}}
    launch = r["spans"].get(spans.LAUNCH)
    if launch:
        out["launch_self_ms"] = (launch["s"] - r["launch_covered_s"]) \
            / launch["n"] * 1e3
        out["launch_covered"] = r["launch_covered_s"] / launch["s"]
    if sum(r["idle_s"]):
        out["idle_in_launch"] = sum(r["idle_in_launch_s"]) / sum(r["idle_s"])
    c = kept.get("counters")
    if c:
        out["counters"] = c
        if c["flushed_requests"]:
            out["queue_wait_ms"] = c["queue_wait_s"] \
                / c["flushed_requests"] * 1e3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
