#!/usr/bin/env python3
"""The two readings a cell's limit is set from, in one process.

    python chipbench/tools/limits.py --workload <cell> --seeds 1,2,... \
        --seconds 3

For each seed: make the model, build the plan, warm up, run a short window
of the cell's own traffic, and read every number compared
(``bench.NUMBERS``) on the sample a run compares: as the program served it
(the lower reading is the largest over the seeds) and with each of the
configuration's ``controls``, the reference one step below a precision it
states, in the program's place (the upper reading is the smallest).
Prints one JSON line per seed and a summary.
"""
import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

from harness import bench, loader  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default="3")
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu":
        print("limits: needs a TPU", file=sys.stderr)
        return 2
    wl = loader.workload(args.workload)
    cfg = loader.config(wl["config"])
    bench.use_bindings(tempfile.mkdtemp(prefix="chipbench-"),
                       loader.bindings_path(cfg["name"]))
    controls = cfg["controls"]
    lower = {k: [] for k in bench.NUMBERS}
    upper = {c: {k: [] for k in bench.NUMBERS} for c in controls}
    for seed in args.seeds.split(","):
        t = time.perf_counter()
        run = bench.Run(bench.parse(["--workload", args.workload,
                                     "--seed", seed,
                                     "--seconds", args.seconds]),
                        wl, cfg, t)
        run.device_kind = jax.devices()[0].device_kind
        run.model = run.family.Model(cfg, run.seed)
        run.plan = run.model.plan()
        traffic = loader.traffic(wl["traffic"]).Traffic(run)
        traffic.setup()
        w = traffic.window(float(args.seconds))
        traffic.close()
        pairs = traffic.sample(w)
        prog = bench.compare(run, pairs, names=bench.NUMBERS)
        ctrl = {c: bench.compare(run, pairs, control=c, names=bench.NUMBERS)
                for c in controls}
        for k in bench.NUMBERS:
            lower[k].append(prog[k])
            for c in controls:
                upper[c][k].append(ctrl[c][k])
        print(json.dumps({"seed": int(seed), "program": prog,
                          "controls": ctrl, "attempted": w["attempted"],
                          "failed": w["failed"],
                          "rows_compared": sum(len(x) for x, _ in pairs),
                          "seconds": time.perf_counter() - t}), flush=True)
        del traffic, run, pairs, w
    print(json.dumps({
        "workload": args.workload,
        "lower_reading": {k: max(v) for k, v in lower.items()},
        "upper_reading": {c: {k: min(v) for k, v in u.items()}
                          for c, u in upper.items()},
        "limits": wl["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
