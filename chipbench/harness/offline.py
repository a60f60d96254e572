"""Closed back-to-back ``ExecutionPlan.run`` calls on one offline batch shape.

A backlog scored in large batches: the cell's data file gives the rows per
call.  A pool of ``POOL`` batches of standard-normal rows is made on the
device from the seed, in one program per batch (row-major float32, the
layout a batch put on the device from host rows has), and cycled.  Each
call's logits go to the host before the next call starts, so the window
counts the device-to-host copy and the dispatch gap as a user of
``plan.run`` would see them.  The frontend and the micro-batcher are
bypassed.
"""
from __future__ import annotations

import functools
import time

import jax
import numpy as np

from .trace import WINDOW_SPAN

POOL = 2           # batches made at set-up and cycled
CHECK_CALLS = 3    # calls whose logits are checked: the first and a sample


@functools.partial(jax.jit, static_argnames=("shape",))
def _batch(key, shape):
    return jax.random.normal(key, shape, np.float32)


class Offline:
    def __init__(self, run):
        self.run = run
        self.rows = int(run.params["rows"])
        self.pool = POOL
        self.keep = CHECK_CALLS

    def setup(self) -> None:
        run = self.run
        key = run.family.seed_key(run.seed, 1)
        self.batches = [_batch(jax.random.fold_in(key, i),
                               (self.rows, run.model.d_in))
                        for i in range(self.pool)]
        jax.block_until_ready(self.batches)
        for _ in range(2):
            np.asarray(run.plan.run(self.batches[0]))

    def window(self, seconds: float, rate=None) -> dict:
        plan, batches, pool = self.run.plan, self.batches, self.pool
        rng = self.run.rng(4)
        # the first call's logits, and a reservoir sample of the others
        first, sample = None, []
        clock = time.perf_counter
        n = 0
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            t0 = clock()
            while True:
                y = np.asarray(plan.run(batches[n % pool]))
                if n == 0:
                    first = (0, y)
                elif len(sample) < self.keep - 1:
                    sample.append((n, y))
                else:
                    j = int(rng.integers(0, n))
                    if j < len(sample):
                        sample[j] = (n, y)
                n += 1
                if clock() - t0 >= seconds:
                    break
            t1 = clock()
        window_s = t1 - t0
        return {"attempted": n, "failed": 0, "failed_check": 0,
                "window_s": window_s, "rows_done": n * self.rows,
                "rows_per_s": n * self.rows / window_s,
                "kernel_calls": n, "kernel_rows": n * self.rows,
                "kept": dict([first] + sample)}

    def summary(self, w: dict) -> dict:
        return {"calls": w["attempted"], "rows_per_call": self.rows,
                "window_s": w["window_s"], "rows_per_s": w["rows_per_s"],
                "checked_calls": sorted(w["kept"])}

    def close(self) -> None:
        pass

    def sample(self, w: dict) -> list:
        """(batch, host logits) of the kept calls."""
        return [(self.batches[n % self.pool], y)
                for n, y in sorted(w["kept"].items())]
