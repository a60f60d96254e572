#!/usr/bin/env python3
"""Record the block bindings of every configuration the cells use, by one
sweep on the chip.

    python chipbench/tools/record_bindings.py [--out FILE]

Builds each configuration's serving plan against an empty autotune file,
so the program sweeps (schedule, block_m) for every bucket on this chip,
and writes the file to ``chipbench/bindings/autotune.json`` (and to
``--out``).  Runs leave that file alone: set-up then runs no sweep.  A
change that should move a binding is invisible until a benchmark change
records them again.
"""
import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

from harness import loader  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu":
        print("record_bindings: needs a TPU", file=sys.stderr)
        return 2
    from repro.kernels import autotune
    from repro.launch import compile_cache
    target = os.path.join(HERE, "bindings", "autotune.json")
    path = os.path.join(HERE, "bindings", "autotune.recording.json")
    if os.path.exists(path):
        os.remove(path)
    os.environ[autotune.ENV_CACHE] = path
    autotune.clear_memory_cache()
    compile_cache.enable()
    configs = sorted({w["config"] for w in loader.spec()["workloads"]})
    for name in configs:
        cfg = loader.config(name)
        t = time.perf_counter()
        with autotune.collect_failures([]) as failures:
            plan = loader.family(cfg["family"]).Model(cfg, 0).plan()
        d = plan.describe()
        print(json.dumps({"config": name, "seconds":
                          time.perf_counter() - t, "failures": failures,
                          **{k: d[k] for k in (
                              "block_m", "bucket_schedules",
                              "bucket_block_m", "default_path")}}),
              flush=True)
        if failures:
            return 1
    shutil.move(path, target)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        shutil.copyfile(target, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
