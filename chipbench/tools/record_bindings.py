#!/usr/bin/env python3
"""Record one configuration's block bindings, by one sweep on the chip.

    python chipbench/tools/record_bindings.py --config <name> [--out FILE]

Builds the configuration's serving plan against an empty autotune file,
so the program sweeps (schedule, block_m) for every bucket on this chip,
runs the warm-up of every cell of that configuration in
``BENCHMARK.json`` (a program may bind a block on its first call), and
writes the file to ``chipbench/bindings/<name>.json`` (and to
``--out``).  Every other configuration's file is left as it is.  Runs
leave the file alone: set-up then runs no sweep.  A change that should
move a binding is invisible until a benchmark change records it again.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from typing import List, Sequence

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

from harness import bench, loader  # noqa: E402


def cells_of(config_name: str) -> List[dict]:
    """The workloads of ``BENCHMARK.json`` that run ``config_name``."""
    return [loader.workload(w["name"]) for w in loader.spec()["workloads"]
            if w["config"] == config_name]


def warm_up(cfg: dict, model, plan, wl: dict) -> None:
    """Run cell ``wl``'s set-up traffic on ``plan``, as a run does."""
    run = bench.Run(bench.parse(["--workload", wl["name"], "--seed", "0",
                                 "--seconds", "1"]), wl, cfg,
                    time.perf_counter())
    run.model, run.plan = model, plan
    traffic = loader.traffic(wl["traffic"]).Traffic(run)
    try:
        traffic.setup()
    finally:
        traffic.close()


def record(cfg: dict, target: str, cells: Sequence[dict] = ()) -> dict:
    """Build ``cfg``'s plan against an empty autotune file, run the warm-up
    of each of ``cells`` on it and, if no candidate failed, write what the
    build and the warm-ups bound to ``target``.  Returns the plan's
    ``describe()`` entries that a run logs, and the failures."""
    from repro.kernels import autotune
    family = loader.family(cfg["family"])
    with tempfile.TemporaryDirectory(prefix="chipbench-") as tmp:
        path = os.path.join(tmp, "autotune.json")
        os.environ[autotune.ENV_CACHE] = path
        autotune.clear_memory_cache()
        t = time.perf_counter()
        with autotune.collect_failures([]) as failures:
            model = family.Model(cfg, 0)
            plan = model.plan()
            for wl in cells:
                warm_up(cfg, model, plan, wl)
        d = plan.describe()
        line = {"config": cfg["name"], "seconds": time.perf_counter() - t,
                "failures": failures,
                **{k: d[k] for k in family.PLAN_KEYS}}
        if not failures:
            if not os.path.exists(path):     # the build bound nothing
                with open(path, "w") as f:
                    json.dump({}, f)
            shutil.copyfile(path, target)
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu":
        print("record_bindings: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch import compile_cache
    compile_cache.enable()
    target = loader.bindings_path(args.config)
    line = record(loader.config(args.config), target, cells_of(args.config))
    print(json.dumps(line), flush=True)
    if line["failures"]:
        return 1
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        shutil.copyfile(target, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
