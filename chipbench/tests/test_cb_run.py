"""A whole run on the CPU at a tiny size, with the look for a chip
skipped: correct on the program as it is, not correct with an answer
altered where the kernel produces it; and no result without a TPU."""
from cbtest import isolated_autotune  # noqa: F401  (autouse)
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import bench, loader

BENCH = loader.BENCH
TINY = dict(d_in=64, features=[128, 64, 12], act_dtype="int8",
            matmul_precision="highest", controls=["int4", "high", "default"],
            max_bucket=8, family="mlp4bit", name="tiny")
TINY_FP32 = dict(d_in=80, features=[64, 10], act_dtype="float32",
                 matmul_precision="highest", controls=["high"], max_bucket=8,
                 family="mlp4bit", name="tiny-fp32")
# case: (cell, config, the cell's params changed)
CELLS = {
    "gsc-int8-online": ("gsc-int8-online", TINY, dict(rate=200)),
    "gsc-int8-online-streams2": ("gsc-int8-online", TINY,
                                 dict(rate=200, streams=2)),
    "gsc-int8-offline": ("gsc-int8-offline", TINY, dict(rows=40)),
    "lenet-fp32-offline": ("lenet-fp32-offline", TINY_FP32, dict(rows=40)),
}


@pytest.fixture(autouse=True)
def small_traffic(monkeypatch):
    """The traffic's fixed sizes, cut to what the CPU serves quickly."""
    from harness import openloop
    monkeypatch.setattr(openloop, "POOL_ROWS", 64)
    monkeypatch.setattr(openloop, "CHECK_REQUESTS", 64)
    monkeypatch.setattr(openloop, "WARM_SECONDS", 0.3)


def run_tiny(case, capsys, seed=2 ** 31 + 7, **more):
    cell, config, params = CELLS[case]
    wl = copy.deepcopy(loader.workload(cell))
    wl["params"].update(params, **more)
    wl["chips"] = 1
    rc = bench.execute(["--workload", cell, "--seed", str(seed),
                        "--seconds", "0.5", "--trace", "0"],
                       require_chip=False, workload=wl, config=config)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_tiny_run_is_correct(cell, capsys):
    res = run_tiny(cell, capsys)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "setup_s" in res["metrics"]
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_altered_answer_is_not_correct(cell, capsys, monkeypatch):
    """One logit altered where the megakernel produces it."""
    from repro.kernels import ops

    real = ops.fantastic4_mlp_fused

    def altered(*a, **k):
        y = real(*a, **k)
        return y.at[0, 0].add(1.0)
    monkeypatch.setattr(ops, "fantastic4_mlp_fused", altered)
    res = run_tiny(cell, capsys)
    assert res["correct"] is False
    assert res["checks"]["max_rel_err"]["value"] > \
        res["checks"]["max_rel_err"]["limit"]


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "gsc-int8-online", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_sampled_rows_are_the_served_rows(capsys, monkeypatch):
    """The check compares each served row with the reference of the row
    that was sent: rows scattered to the wrong request fail it."""
    from repro.serving import batcher

    real = batcher.MicroBatcher.execute

    def swapped(self, t, **k):
        done, bucket, dt = real(self, t, **k)
        if len(done) > 1:
            done[0].y, done[1].y = done[1].y, done[0].y
        return done, bucket, dt
    monkeypatch.setattr(batcher.MicroBatcher, "execute", swapped)
    res = run_tiny("gsc-int8-online", capsys, seed=5, rate=2000)
    assert res["correct"] is False
    assert np.isfinite(res["checks"]["max_rel_err"]["value"])
