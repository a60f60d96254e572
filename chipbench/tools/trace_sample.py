#!/usr/bin/env python3
"""Trace a few ``plan.run`` calls of one configuration and print the
trace's planes, lines and events, and its reduction.

    python chipbench/tools/trace_sample.py --config <config> --rows <n> \
        --calls <n> --out <dir>

Look at a trace by hand with this before trusting what the reduction in
``harness/trace.py`` reads from it.  ``--out`` keeps the trace.
"""
import argparse
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

from harness import bench, loader, trace  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    import numpy as np
    if jax.default_backend() != "tpu":
        print("trace_sample: needs a TPU", file=sys.stderr)
        return 2
    bench.use_bindings(tempfile.mkdtemp(prefix="chipbench-"))
    cfg = loader.config(args.config)
    model = loader.family(cfg["family"]).Model(cfg, 0)
    plan = model.plan()
    # rows made on the host, row-major, as a user's rows arrive
    x = jax.device_put(np.random.default_rng(1).standard_normal(
        (args.rows, model.d_in), dtype=np.float32))
    np.asarray(plan.run(x))
    jax.profiler.start_trace(args.out,
                             profiler_options=bench._trace_options())
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(args.calls):
            np.asarray(plan.run(x))
    jax.profiler.stop_trace()
    trace.describe(args.out)
    print(trace.reduce(trace.load(args.out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
