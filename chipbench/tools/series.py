#!/usr/bin/env python3
"""Run one cell several times, one process per seed, one after another.

    python chipbench/tools/series.py --workload <cell> --seeds 11,12,13 \
        --seconds 10 [--trace 1] [--out DIR]

Each run's standard output and error go to ``DIR/<cell>.<seed>.<trace>.
{out,err}``; a line per run and, at the end, each metric's median and
spread (interquartile distance over median) are printed.  This process
never imports JAX: each run holds the chips alone.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "series"))
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    values = {}
    for seed in args.seeds.split(","):
        stem = os.path.join(args.out,
                            f"{args.workload}.{seed}.{args.trace}")
        t = time.perf_counter()
        with open(stem + ".out", "w") as out, open(stem + ".err", "w") as err:
            rc = subprocess.call(
                [sys.executable, os.path.join(HERE, "run.py"),
                 "--workload", args.workload, "--seed", seed,
                 "--seconds", args.seconds, "--trace", args.trace],
                cwd=ROOT, stdout=out, stderr=err)
        wall = time.perf_counter() - t
        with open(stem + ".out") as f:
            lines = f.read().strip().splitlines()
        res = json.loads(lines[-1]) if rc == 0 and lines else {}
        line = {"seed": seed, "rc": rc, "wall_s": round(wall, 3),
                "correct": res.get("correct"),
                "attempted": res.get("attempted"),
                "failed": res.get("failed"),
                "metrics": {k: v["value"]
                            for k, v in res.get("metrics", {}).items()},
                "checks": res.get("checks"),
                "memory_peak_bytes": res.get("device", {}).get(
                    "memory_peak_bytes")}
        if "breakdown" in res:
            line["busy_s"] = res["device"]["busy_s"]
            line["window_s"] = res["device"]["window_s"]
            line["breakdown"] = res["breakdown"]
        print(json.dumps(line), flush=True)
        if rc != 0:
            with open(stem + ".err") as f:
                print(f.read()[-3000:], flush=True)
        for k, v in line["metrics"].items():
            values.setdefault(k, []).append(v)
    for k, vs in values.items():
        med = statistics.median(vs)
        spread = None
        if len(vs) >= 2:
            q1, q2, q3 = statistics.quantiles(vs, n=4)
            spread = (q3 - q1) / q2 if q2 else None
        print(json.dumps({"metric": k, "n": len(vs), "median": med,
                          "spread": spread, "values": vs}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
