#!/usr/bin/env python3
"""Run one cell traced, as ``run.py --trace 1`` does, and split its host
time by the program's ``serving.*`` spans.

    python chipbench/tools/span_split.py --workload <cell> --seed <n> \
        --seconds <s> [--out <dir>]

Prints ``run.py``'s own result line, then one JSON line: the cost of one
span on this host with no trace active (plain, and with the metadata a
launch sets); the cell's per-layer metrics as the harness's readers give
them; the harness's reduction of the window's spans (``harness/spans.py``,
as the readers find it); what no reader gives: the mean milliseconds of
every span, the launch's self time (its duration less the union of its
child spans) and the share the children cover; and, for open-loop
traffic, the micro-batcher's counters over the window.  ``--out`` keeps
the trace.
"""
import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

from harness import bench, loader, spans  # noqa: E402

COUNTERS = ("launches", "flushed_requests", "queue_wait_s")


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


def split(r: dict) -> dict:
    """What no metric reader gives of one traced window's span reduction
    ``r`` (``spans.reduce``)."""
    out = {"spans": r,
           "mean_ms": {k: spans.mean_ms(r, k) for k in r["spans"]}}
    launch = r["spans"].get(spans.LAUNCH)
    if launch:
        out["launch_self_ms"] = (launch["s"] - r["launch_covered_s"]) \
            / launch["n"] * 1e3
        out["launch_covered"] = r["launch_covered_s"] / launch["s"]
    return out


def report(readings, names) -> dict:
    """The metrics ``names`` as the harness's readers read ``readings``,
    the batcher's counters, and the split of the window's spans."""
    out = {"metrics": {n: loader.metric_reader(n).read(readings)
                       for n in names}}
    counters = {k: readings.window[k] for k in COUNTERS
                if k in readings.window}
    if counters:
        out["counters"] = counters
    return {**out, **split(readings.trace["spans"])}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    cost = span_cost_us()
    kept = {}

    def keep(readings):
        names = [m["name"] for m in loader.metrics_for(
            loader.spec(), args.workload, True)]
        kept["split"] = report(readings, names)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            shutil.copy(readings.xspace, args.out)

    rc = bench.execute(["--workload", args.workload, "--seed",
                        str(args.seed), "--seconds", str(args.seconds),
                        "--trace", "1"], on_readings=keep)
    if rc or "split" not in kept:
        return rc or 1
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "cost": cost, **kept["split"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
