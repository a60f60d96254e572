#!/usr/bin/env python3
"""Drive the 4-bit serving path once on the TPU and check what it serves.

    python chip_smoke.py [--seed N]              # one chip
    python chip_smoke.py --chips 4 [--seed N]    # the multi-chip paths only

One chip, all in this process:

  (a) freeze MLP-GSC at the paper's widths (512-512-512-256-256-128-128-12)
      from random weights made from ``--seed``;
  (b) build an fp32 and an int8 plan with ``mode="auto"`` — on the TPU that
      runs the timed (schedule, block_m) sweep of every bucket;
  (c) serve ragged single-row requests through a ``ServingFrontend`` in
      groups of 1, 8, 64 and 256 rows;
  (d) check every served row against ``plan.run`` and against a plain fp32
      reference under ``jax.default_matmul_precision("highest")`` (max
      relative error <= 1e-3, relative to the largest reference value);
      for int8, check the megakernel against the int8 per-layer chain bit
      for bit wherever the chain sums K in one block, and print the share
      and size of the differences where it does not.

``--chips 4`` runs only what exists across chips: MLP-GSC as a
``mode="sharded"`` plan on the 2x2 mesh, compared bit for bit with the
one-device per-layer chain (fp32 and int8), and ``ServingFrontend(streams=4)``
against ``plan.run``, with the launches and operand devices of each stream.

A sweep candidate that fails to compile, a launch that fails or falls back,
a wrong result, or no TPU at all exits non-zero without the result line.
The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Compiled programs go to JAX's persistent cache and bindings to the
autotuner's JSON, both at fixed places (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GROUPS = (1, 8, 64, 256)          # rows per group of single-row requests
RTOL = 1e-3
MAX_DELAY_S = 0.5                 # long enough to coalesce a whole group


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit replaces a compile by a read)."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"compile {self.seconds:.2f} s, persistent-cache hits "
                f"{self.hits}, misses {self.misses}")


# ------------------------------------------------------------------ model

def freeze_gsc(seed: int) -> dict:
    import jax
    from repro.configs.paper_mlps import MLP_GSC
    from repro.core import qat
    from repro.models import mlp as M
    params, bn = M.mlp_init(jax.random.PRNGKey(seed), MLP_GSC)
    return M.freeze_mlp(params, qat.build_qstate(params), bn,
                        lam=MLP_GSC.lam)


def decode_numpy(packed, omega) -> np.ndarray:
    """Row-pair packed 4-bit codes -> W = sum_i omega_i B_i, in numpy."""
    p = np.asarray(packed)
    codes = np.stack([p & 0xF, p >> 4], axis=1).reshape(-1, p.shape[1])
    bits = (codes[..., None] >> np.arange(4)) & 1
    return bits.astype(np.float32) @ np.asarray(omega, np.float32)


def reference(pack: dict, x: np.ndarray, act_scales=None) -> np.ndarray:
    """Plain fp32 forward of the frozen pack, apart from the kernels: the
    int8 variant re-quantizes between layers at ``act_scales`` and folds
    each scale into the next layer's alpha1, as the serving paths do."""
    import jax
    import jax.numpy as jnp
    layers = pack["layers"]
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(x, jnp.float32)
        in_scale = 1.0
        for i, layer in enumerate(layers):
            k, _ = layer["shape"]
            check(layer["activation"] in (None, "relu"),
                  f"reference has no activation {layer['activation']!r}")
            w = jnp.asarray(decode_numpy(layer["packed"], layer["omega"])[:k])
            y = (h @ w) * (layer["alpha1"] * in_scale) + layer["bias"]
            if layer["activation"] == "relu":
                y = jnp.maximum(y, 0.0)
            if act_scales is None:
                y = y * layer["alpha2"]
            elif i < len(layers) - 1:
                y = jnp.clip(jnp.round(y / act_scales[i]), -127.0, 127.0)
                in_scale = act_scales[i]
            h = y
        return np.asarray(h)


def rel_err(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# -------------------------------------------------------------- one chip

def report_plan(name: str, plan, seconds: float, meter: CompileMeter) -> None:
    d = plan.describe()
    print(f"{name}: mode {d['resolved_mode']}, interpret {d['interpret']}, "
          f"block_m {d['block_m']} [{d['block_source']}], built in "
          f"{seconds:.2f} s ({meter.line()} so far)", flush=True)
    for b in d["bucket_sizes"]:
        print(f"{name}:   bucket {b}: {d['bucket_schedules'][b]} "
              f"block_m {d['bucket_block_m'][b]} [{d['bucket_sources'][b]}]")
    for note in d["notes"]:
        print(f"{name}: note: {note}")
    check(not d["interpret"], f"{name}: plan runs the Pallas interpreter")
    check(d["resolved_mode"] == "fused", f"{name}: not the megakernel")
    for b, path in d["bucket_paths"].items():
        check(path.startswith("fused"), f"{name}: bucket {b} binds {path}")
    failed = [n for n in d["notes"] if n.startswith("sweep candidate failed")]
    check(not failed, f"{name}: {len(failed)} sweep candidates failed")


def serve_groups(name: str, plan, rng, act_scales=None) -> None:
    """(c) + (d): each group as single-row requests through a frontend."""
    import jax.numpy as jnp
    from repro import serving
    fe = serving.ServingFrontend().start()
    try:
        fe.register(name, plan, max_delay=MAX_DELAY_S)
        for rows in GROUPS:
            xs = rng.normal(size=(rows, plan.d_in)).astype(np.float32)
            t0 = time.perf_counter()
            futs = [fe.submit(name, xs[i:i + 1]) for i in range(rows)]
            outs = [f.result(timeout=600) for f in futs]
            dt = time.perf_counter() - t0
            bad = [o for o in outs if isinstance(o, serving.Rejected)]
            check(not bad, f"{name}: rejected {bad[:1]}")
            served = np.concatenate([o.y for o in outs])
            direct = np.asarray(plan.run(jnp.asarray(xs)))
            ref = reference(plan.pack, xs, act_scales)
            e_run, e_ref = rel_err(served, direct), rel_err(served, ref)
            print(f"{name}: {rows} requests -> buckets "
                  f"{sorted({o.bucket for o in outs})} in {dt:.3f} s; "
                  f"vs plan.run rel {e_run:.3g} (bitwise "
                  f"{np.array_equal(served, direct)}), vs reference rel "
                  f"{e_ref:.3g}", flush=True)
            check(e_run <= RTOL, f"{name}: served vs plan.run {e_run}")
            check(e_ref <= RTOL, f"{name}: served vs reference {e_ref}")
        st = fe.stats
        check(st["launch_failures"] == 0 and st["fallbacks"] == 0,
              f"{name}: launch failures {st['launch_failures']}, "
              f"fallbacks {st['fallbacks']}")
    finally:
        fe.close()


def int8_chain_parity(plan, rng) -> None:
    """The int8 megakernel against the int8 per-layer chain."""
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    for rows in GROUPS:
        x = jnp.asarray(rng.normal(size=(rows, plan.d_in)), jnp.float32)
        fused = np.asarray(plan.run(x))
        chain = np.asarray(kops.fantastic4_mlp_chain_int8(
            x, plan.layers, plan.act_scales))
        split = []
        for layer in plan.layers:
            k, n = layer["shape"]
            cfg = kops.matmul_blocks(rows, k + k % 2, n,
                                     interpret=plan.interpret,
                                     activation=layer["activation"])
            if cfg.block_k < -(-k // 128) * 128:
                split.append((k, n, cfg.block_k))
        diff = fused != chain
        print(f"int8: {rows} rows, schedule {plan.schedule_for(rows)}: "
              f"megakernel vs chain differ in {diff.mean():.4%} of logits "
              f"(max {np.max(np.abs(fused - chain)):.3g}); chain K split "
              f"in layers {split or 'none'}", flush=True)
        if not split:
            check(not diff.any(), f"int8: megakernel != chain at {rows} rows")


def one_chip(seed: int, meter: CompileMeter) -> None:
    import jax.numpy as jnp
    from repro import serving
    t0 = time.perf_counter()
    pack = freeze_gsc(seed)
    print(f"freeze: MLP-GSC {[l['shape'] for l in pack['layers']]} in "
          f"{time.perf_counter() - t0:.2f} s", flush=True)
    rng = np.random.default_rng(seed)
    calib_x = jnp.asarray(rng.normal(size=(64, 512)), jnp.float32)
    for act_dtype in ("float32", "int8"):
        t0 = time.perf_counter()
        plan = serving.build_plan(
            pack, mode="auto", act_dtype=act_dtype,
            calib_x=calib_x if act_dtype == "int8" else None)
        report_plan(act_dtype, plan, time.perf_counter() - t0, meter)
        t0 = time.perf_counter()
        plan.warmup()
        print(f"{act_dtype}: warm-up of {len(plan.bucket_sizes)} buckets "
              f"{time.perf_counter() - t0:.2f} s", flush=True)
        serve_groups(act_dtype, plan, rng, plan.act_scales)
        if act_dtype == "int8":
            int8_chain_parity(plan, rng)


# ------------------------------------------------------------ four chips

def four_chips(seed: int) -> None:
    pack = freeze_gsc(seed)
    rng = np.random.default_rng(seed)
    sharded_parity(pack, rng)
    streams_parity(pack, rng)


def sharded_parity(pack: dict, rng) -> None:
    """The sharded plan on the 2x2 mesh against the one-device chain."""
    import jax.numpy as jnp
    from repro import serving
    from repro.launch.mesh import fit_mesh
    calib_x = jnp.asarray(rng.normal(size=(64, 512)), jnp.float32)
    mesh = fit_mesh()
    check(mesh.devices.size == 4, f"mesh over {mesh.devices.size} devices")
    for act_dtype in ("float32", "int8"):
        kw = {"act_dtype": act_dtype,
              "calib_x": calib_x if act_dtype == "int8" else None}
        chain = serving.build_plan(pack, mode="per_layer", **kw)
        sharded = serving.build_plan(pack, mode="sharded", mesh=mesh, **kw)
        print(f"sharded {act_dtype}: {sharded.describe()['sharding']}")
        for rows in GROUPS:
            x = jnp.asarray(rng.normal(size=(rows, 512)), jnp.float32)
            a, b = np.asarray(chain.run(x)), np.asarray(sharded.run(x))
            print(f"sharded {act_dtype}: {rows} rows, bit-identical to the "
                  f"one-device chain: {np.array_equal(a, b)} (max diff "
                  f"{np.max(np.abs(a - b)):.3g})", flush=True)
            check(np.array_equal(a, b),
                  f"sharded {act_dtype} != chain at {rows} rows")



def streams_parity(pack: dict, rng) -> None:
    """A 4-stream frontend against ``plan.run``, with where each stream's
    launches ran."""
    import jax.numpy as jnp
    from repro import serving
    plan = serving.build_plan(pack, mode="auto")
    fe = serving.ServingFrontend(streams=4).start()
    try:
        fe.register("gsc", plan, max_delay=2e-3)
        xs = rng.normal(size=(1024, 512)).astype(np.float32)
        outs = [f.result(timeout=600) for f in
                [fe.submit("gsc", xs[i:i + 1]) for i in range(len(xs))]]
        st = fe.stats
    finally:
        fe.close()
    check(not any(isinstance(o, serving.Rejected) for o in outs),
          "streams: a request was rejected")
    served = np.concatenate([o.y for o in outs])
    direct = np.asarray(plan.run(jnp.asarray(xs)))
    e = rel_err(served, direct)
    operand_devices = sorted({str(d) for layer in plan.layers
                              for d in layer["packed"].devices()})
    for i, ss in enumerate(st["streams"]):
        # where this stream's own launches held their batch and result,
        # as its worker recorded them
        print(f"streams: stream {i} on {ss['device']}: {ss['launches']} "
              f"launches, {ss['launch_failures']} failures; its batches on "
              f"{ss['batch_devices']}, its results on {ss['result_devices']}")
        if ss["launches"]:
            check(ss["batch_devices"] == ss["result_devices"]
                  == [ss["device"]], f"streams: stream {i} on "
                  f"{ss['device']} computed on {ss['result_devices']}")
    print(f"streams: pack held on {operand_devices} and copied to each "
          f"stream's device; served vs plan.run rel {e:.3g} (bitwise "
          f"{np.array_equal(served, direct)})", flush=True)
    check(e <= RTOL, f"streams: served vs plan.run {e}")
    check(st["launch_failures"] == 0 and st["fallbacks"] == 0,
          "streams: launch failures or fallbacks")
    used = {ss["device"] for ss in st["streams"] if ss["launches"]}
    check(len(used) > 1, f"streams: every launch landed on {used}")


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path on one chip; 4: the sharded "
                         "plan and the 4-stream frontend only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's backend is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; JAX has {len(devices)}", file=sys.stderr)
        return 2
    from repro.kernels import autotune
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    meter = CompileMeter()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    print(f"compile cache: {cache_dir}; autotune cache: "
          f"{autotune.cache_path()}", flush=True)
    t0 = time.perf_counter()
    try:
        # every failed sweep candidate, the per-layer chain's included
        with autotune.collect_failures([]) as failures:
            if args.chips == 4:
                four_chips(args.seed)
            else:
                one_chip(args.seed, meter)
        for msg in failures:
            print(msg, file=sys.stderr)
        check(not failures, f"{len(failures)} sweep candidates failed")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total {time.perf_counter() - t0:.2f} s; {meter.line()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
