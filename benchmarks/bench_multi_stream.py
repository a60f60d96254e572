"""Scale-out serving: N replicated execution streams, A/B'd against one.

The single-stream engine (bench_serving_engine) is arrival-rate-bound at
low load and service-rate-bound under backlog; replicating the execution
stream moves the service-rate ceiling.  This benchmark replays the same
seeded Poisson trace through ``serving.replay`` at ``n_streams`` ∈
{1, 2, 4} over one fused ``ExecutionPlan`` and reports the aggregate
throughput gain vs the 1-stream baseline at each offered load.

The virtual clock uses a *monotone* per-bucket service-time table (the
running max of the calibrated table over increasing buckets): on a noisy
interpret host a larger bucket occasionally times faster than a smaller
one, and a non-monotone table would let the multi-stream replay "win" by
bucket-split luck rather than by parallel service.  The same table drives
every leg, so the A/B is deterministic.

Every leg runs with ``max_bucket=16``: uncapped, deep backlog coalesces
into ever-larger tiles whose sub-linear per-row cost lets ONE stream
absorb any load — mathematically tidy, but it is exactly the
latency-unbounded regime serving avoids (a 256-row tile is a 256-row
p95).  Under a bounded bucket the single stream has a hard service-rate
ceiling and replication is what moves it, which is the regime this
benchmark exists to measure.

Two parity legs gate the rows:

* **threads** — a real ``ServingFrontend(streams=2)`` (dispatch thread +
  2 workers, join-shortest-estimated-work) serves ragged int8 traffic;
  every result must be bit-identical to the per-request ``plan.run``.
* **sharded** — a subprocess with ``--xla_force_host_platform_device_count=4``
  builds the same seeded pack as ``mode="sharded"`` over ``fit_mesh()``
  and checks the column-split program is bit-identical to the per-layer
  chain on the int8 grid.

Extends the repo-root ``BENCH_fused_serving.json`` with a
``multi_stream_rows`` section (guarded by scripts/check_bench_rows.py on
row identity and ``aggregate_gain``); also writes
results/bench/multi_stream.json.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.bench_fused_serving import _rand_pack, merge_root_json
from benchmarks.bench_serving_engine import (MAX_DELAY_S, _requests,
                                             _service_table)
from benchmarks.common import save, topology
from repro import serving
from repro.configs.paper_mlps import MLP_GSC

# offered load as a fraction of ONE capped stream's peak row service rate
# (MAX_BUCKET rows per t_16): 0.3/1.0 bracket the keep-up regime, 4/10
# oversubscribe a single stream so replication is load-bearing.  Defining
# load against t_single (as bench_serving_engine does) would leave the
# capped stream ~13x underutilized at "load 10".
LOADS = (0.3, 1.0, 4.0, 10.0)
STREAMS = (1, 2, 4)
MAX_BUCKET = 16                          # latency-bounded tiles (docstring)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# run in a subprocess: device count is fixed at backend init, so a
# 4-device mesh needs its own XLA_FLAGS before the first jax import.
_SHARDED_PARITY_CODE = r'''
import json
import jax
import jax.numpy as jnp
import numpy as np
from benchmarks.bench_fused_serving import _rand_pack
from repro import serving
from repro.configs.paper_mlps import MLP_HR
from repro.launch.mesh import fit_mesh

cfg = MLP_HR
pack = _rand_pack(cfg)
calib_x = jnp.asarray(np.random.default_rng(3).normal(size=(32, cfg.d_in)),
                      jnp.float32)
scales = serving.calibrate_act_scales(pack, calib_x)
mesh = fit_mesh()
ref = serving.build_plan(pack, mode="per_layer", act_dtype="int8",
                         calib=scales)
shp = serving.build_plan(pack, mode="sharded", mesh=mesh, act_dtype="int8",
                         calib=scales)
ok = True
for b in (1, 8):
    x = jnp.asarray(np.random.default_rng(b).normal(size=(b, cfg.d_in)),
                    jnp.float32)
    ok = ok and bool(np.array_equal(np.asarray(ref.run(x)),
                                    np.asarray(shp.run(x))))
print(json.dumps({
    "n_devices": int(jax.device_count()),
    "mesh": dict(zip(mesh.axis_names,
                     [int(s) for s in mesh.devices.shape])),
    "sharding": shp.describe()["sharding"],
    "bit_identical": ok}))
'''


def _monotone(table: dict) -> dict:
    """Service time non-decreasing in bucket rows (running max)."""
    mono, t = {}, 0.0
    for b in sorted(table):
        t = max(t, table[b])
        mono[b] = t
    return mono


def _frontend_parity(pack, cfg, n_req: int) -> bool:
    """Real threads: streams=2 frontend vs per-request plan.run, int8."""
    calib_x = jnp.asarray(
        np.random.default_rng(3).normal(size=(32, cfg.d_in)), jnp.float32)
    plan = serving.build_plan(
        pack, mode="fused", act_dtype="int8",
        calib=serving.calibrate_act_scales(pack, calib_x))
    xs = _requests(cfg, n_req, seed=5)
    fe = serving.ServingFrontend(streams=2).start()
    try:
        fe.register("gsc", plan, max_delay=1e-3)
        futs = [fe.submit("gsc", x) for x in xs]
        outs = [f.result(timeout=120) for f in futs]
    finally:
        fe.close()
    used = {getattr(o, "stream", None) for o in outs}
    print(f"threads parity: {len(outs)} requests over streams {sorted(used)}",
          flush=True)
    for x, out in zip(xs, outs):
        if isinstance(out, serving.Rejected):
            return False
        np.testing.assert_array_equal(np.asarray(out.y),
                                      np.asarray(plan.run(x)))
    return True


def _sharded_parity() -> dict:
    # the child is a CPU leg on forced host devices: pinned to the CPU so it
    # never reaches for an accelerator this process may already hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO_ROOT, os.path.join(REPO_ROOT, "src"),
                    env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, "-c", _SHARDED_PARITY_CODE],
                          cwd=REPO_ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded parity leg failed:\n{proc.stderr}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    print(f"sharded parity: {out['n_devices']} devices, mesh {out['mesh']}, "
          f"col-split layers {out['sharding']['col_sharded_layers']}, "
          f"bit_identical={out['bit_identical']}", flush=True)
    return out


def run(fast: bool = False):
    n_req = 64 if fast else 256
    cfg = MLP_GSC
    pack = _rand_pack(cfg)
    plan = serving.build_plan(pack, mode="fused")
    table = _monotone(_service_table(plan, repeats=3 if fast else 5))
    xs = _requests(cfg, n_req, seed=13)
    avg_rows = sum(int(x.shape[0]) for x in xs) / len(xs)
    # one capped stream's peak service rate, in requests/s
    cap_rps = MAX_BUCKET / max(table[MAX_BUCKET], 1e-9) / avg_rows

    rows = []
    for load in LOADS:
        lam = load * cap_rps
        rng = np.random.default_rng(int(load * 100) + 29)
        arrivals = np.cumsum(rng.exponential(1.0 / lam, size=n_req))
        base = None
        for n in STREAMS:
            rep = serving.replay(plan, xs, arrivals, max_delay=MAX_DELAY_S,
                                 max_bucket=MAX_BUCKET, service_times=table,
                                 n_streams=n)
            if n == 1:
                base = rep
            else:
                # replicated streams run the same plan: the scattered
                # results must be identical at any N, only timing moves.
                for a, b in zip(base["results"], rep["results"]):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
            row = {"model": cfg.name, "load": load, "streams": n,
                   "max_bucket": MAX_BUCKET,
                   "throughput_rps": rep["throughput_rps"],
                   "baseline_throughput_rps": base["throughput_rps"],
                   "aggregate_gain": rep["throughput_rps"]
                   / max(base["throughput_rps"], 1e-12),
                   "latency_p95_ms": rep["latency_p95_ms"],
                   "stream_launches": rep["stream_launches"],
                   **topology()}
            rows.append(row)
            print(f"{cfg.name:12s} load={load:<5.1f} streams={n} "
                  f"{row['throughput_rps']:8.1f} req/s "
                  f"({row['aggregate_gain']:.2f}x)  p95 "
                  f"{row['latency_p95_ms']:7.2f} ms  "
                  f"launches={row['stream_launches']}", flush=True)

    not_slower = all(r["aggregate_gain"] >= 1.0 - 1e-9 for r in rows)
    strictly = all(r["aggregate_gain"] > 1.0 for r in rows
                   if r["load"] >= 4 and r["streams"] >= 2)
    assert not_slower, "multi-stream replay slower than single-stream"
    assert strictly, "no multi-stream gain under backlog (load >= 4)"

    threads_ok = _frontend_parity(pack, cfg, n_req=24 if fast else 48)
    assert threads_ok, "streams=2 frontend results diverged from plan.run"
    sharded = _sharded_parity()
    assert sharded["bit_identical"], \
        "sharded plan diverged from the per-layer chain on the int8 grid"

    summary = {
        "backend": jax.default_backend(),
        "multi_stream_loads": list(LOADS),
        "multi_stream_rows": rows,
        "multi_stream_not_slower_everywhere": not_slower,
        "multi_stream_gain_under_backlog": strictly,
        "frontend_threads_bit_identical": threads_ok,
        "sharded_parity": sharded,
    }
    save("multi_stream", summary)
    merge_root_json(summary)
    return summary


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    run(ap.parse_args().fast)
