"""Serving launcher: frozen 4-bit weights, batched greedy decoding.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
        --batch 4 --prompt-len 16 --max-new 16

Loads (or initialises) a model, freezes it to the packed-int4 serving form
(qat.freeze_tree — weights live at 4 bits/weight from then on), runs a
jitted prefill over the prompt batch and a jitted single-token decode loop.
Requests are batched: the decode step advances every sequence in lockstep
(continuous batching's inner loop; slot management would sit above this).

Paper MLP archs (``--arch mlp-gsc | mlp-hr | lenet-300-100``) take the
classification serving path instead: freeze to the packed-int4 pack,
resolve a ``serving.ExecutionPlan`` (mode, autotuned blocks, VMEM-fit
fallback and — with ``--int8`` — activation calibration, all decided once
up front) and run the batch through the plan's bucket entry.  The resolved
plan is validated and printed *before* the timed run, and the run is
labeled by what actually executed, not by the flags: a ``--double-buffer``
request that cannot engage (no ≥16-row tile) or a stack that falls back
past the VMEM budget surfaces as a plan note first.  ``--no-fused``
selects the chained per-layer kernel; ``--engine`` additionally pushes the
batch through the micro-batcher as single-row ragged requests (the
continuous-batching path).

With ``--engine --async`` the ragged requests go through the threaded
``serving.ServingFrontend`` instead of the inline flush — a real-clock
dispatch thread, futures on the submit side — and ``--multi a,b`` freezes
additional paper-MLP packs into the same frontend so several models share
the single execution stream (deadline-FIFO across models; per-model
latency reported).

Robustness knobs on the async path: ``--tier`` / ``--max-delay`` accept
one value or a comma-separated list aligned to ``[--arch] + --multi``
(per-model SLO tier names / coalescing budgets in ms), ``--max-queued``
bounds every model's queue in rows (overflow is a typed
``serving.Rejected``, counted and reported, never a hang), and
``--inject-fault RATE`` wraps every plan in a ``FaultInjector`` so the
frontend's degradation ladder (retry -> chain fallback -> quarantine)
can be watched live; the run reports retries/fallbacks/quarantines and
validates the rows that completed.

Scale-out: ``--streams N`` replicates the async frontend's
execution stream N ways (one per device on a multi-device host —
join-shortest-estimated-work dispatch, per-stream quarantine);
``--shard`` column-shards the plan itself over the host's
``('data','model')`` mesh (``launch.mesh.fit_mesh``) — the two compose
with every robustness knob above.

LM archs accept ``--engine`` too (this PR): the prompt batch is re-served
through the :class:`~repro.serving.lm.LMProgram` servable program — one
megakernel-backed FFN plan set per transformer block, prefill and decode
steps as wire rows through a ``ServingFrontend`` — and the engine's decode
tokens are asserted bit-identical to the program's direct ``generate``
loop.  Dense-attention archs only (the program's contract).

Run as a program, the launcher keeps JAX's compile cache and the block
autotuner's JSON at fixed places (``launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..configs.paper_mlps import MLPS
from ..core import qat
from ..nn import transformer as T
from ..nn.module import QuantCtx
from .. import serving


def _freeze_mlp_pack(cfg, seed: int = 0):
    """Init + freeze one paper MLP to its packed-int4 serving pack."""
    from ..models import mlp as M

    key = jax.random.PRNGKey(seed)
    params, bn = M.mlp_init(key, cfg)
    qs = qat.build_qstate(params)
    pack = M.freeze_mlp(params, qs, bn, lam=cfg.lam)
    summ = M.pack_compression_summary(pack)
    print(f"{cfg.name}: {len(pack['layers'])} layers frozen to "
          f"{summ['compressed_bytes']} bytes "
          f"({summ['compression_ratio']:.1f}x vs fp32), "
          f"formats {summ['formats']}")
    return pack


def _mode_kwargs(args):
    """The plan-mode kwargs the flags resolve to, shared by the primary
    plan, --multi co-served packs and the pack-cache registration path
    (all models must run the requested configuration)."""
    if args.shard:
        from .mesh import fit_mesh
        mesh = fit_mesh()
        print(f"shard: ('data','model') mesh "
              f"{dict(zip(mesh.axis_names, mesh.devices.shape))} over "
              f"{mesh.devices.size} device(s)")
        return {"mode": "sharded", "mesh": mesh}
    return {"mode": "fused" if args.fused else "per_layer"}


def serve_mlp(args):
    """Frozen paper-MLP serving through the unified serving engine."""
    cfg = MLPS[args.arch]
    key = jax.random.PRNGKey(0)
    pack = _freeze_mlp_pack(cfg)

    b = args.batch
    x = jax.random.normal(key, (b, cfg.d_in), jnp.float32)

    args._mode_kwargs = _mode_kwargs(args)
    plan = serving.build_plan(
        pack,
        act_dtype="int8" if args.int8 else "float32",
        double_buffer=args.double_buffer,
        calib_x=x if args.int8 else None,
        **args._mode_kwargs)

    # resolved-plan report BEFORE anything is timed: the label below is
    # what will actually execute for this batch, and every requested-but-
    # not-engaged option surfaces as a note here, not after the numbers.
    desc = plan.describe()
    mode = plan.mode_label(b)
    print(f"plan: requested {desc['requested_mode']}"
          f"{' +double-buffer' if args.double_buffer else ''}"
          f"{' +int8' if args.int8 else ''} -> resolved "
          f"{desc['resolved_mode']} (batch {b}: {mode}; "
          f"block_m {desc['block_m']} [{desc['block_source']}], "
          f"buckets {desc['bucket_sizes']})")
    if desc.get("sharding"):
        sh = desc["sharding"]
        print(f"plan: sharded over {sh['mesh']} — column-split layers "
              f"{sh['col_sharded_layers']}, replicated "
              f"{sh['replicated_layers'] or 'none'}")
    print("plan: bucket -> schedule " + ", ".join(
        f"{bk}:{desc['bucket_schedules'][bk]}"
        f"[bm={desc['bucket_block_m'][bk]},{desc['bucket_sources'][bk]}]"
        for bk in desc["bucket_sizes"]))
    print(f"plan: ws crossover {desc['ws_crossover_rows']} rows "
          f"(prior {desc['ws_prior_rows']} "
          f"[{desc['ws_prior_source']}])")
    for note in desc["notes"]:
        print(f"note: {note}")

    def _run():
        return plan.run(x)

    y = jax.block_until_ready(_run())         # compile (+ autotune) warm-up
    t0 = time.time()
    iters = max(args.iters, 1)
    for _ in range(iters):
        y = _run()
    jax.block_until_ready(y)
    dt = (time.time() - t0) / iters
    print(f"{mode}: {dt*1e3:.2f} ms/batch  "
          f"({b/max(dt, 1e-12):.0f} samples/s, batch {b})")
    print("logits[0]:", np.asarray(y[0]).round(3).tolist())

    if args.engine and args.async_frontend:
        serve_mlp_async(args, cfg, plan, x, y)
    elif args.engine:
        # ragged path: the same batch as b single-row requests through the
        # queue -> bucket -> plan pipeline.  One untimed pass first — the
        # timed number must be a serving figure, not a trace/compile one
        # (bucket entries plus the submit/coalesce/scatter glue ops all
        # compile on first use; the batch path above only warmed its own
        # bucket).
        jax.block_until_ready(
            serving.MicroBatcher(plan).serve(list(x))[-1])
        batcher = serving.MicroBatcher(plan)
        t0 = time.time()
        ys = batcher.serve(list(x))
        jax.block_until_ready(ys[-1])
        dt_e = time.time() - t0
        st = batcher.stats
        print(f"engine (ragged, {st['flushes']} flushes, bucket hist "
              f"{st['bucket_hist']}): {dt_e*1e3:.2f} ms total "
              f"({b/max(dt_e, 1e-12):.0f} samples/s)")
        np.testing.assert_allclose(np.concatenate([np.asarray(v) for v in ys]),
                                   np.asarray(y), atol=1e-5, rtol=1e-5)
    return y


def _per_model(opt, flag, names, cast):
    """Split a one-or-comma-separated flag across the registered models
    (order: [--arch] + --multi).  A single value broadcasts."""
    if not opt:
        return {n: None for n in names}
    vals = opt.split(",")
    if len(vals) == 1:
        vals = vals * len(names)
    if len(vals) != len(names):
        raise SystemExit(f"{flag}: expected 1 or {len(names)} "
                         f"comma-separated values, got {len(vals)}")
    try:
        return {n: cast(v) for n, v in zip(names, vals)}
    except ValueError as e:
        raise SystemExit(f"{flag}: {e}")


def serve_mlp_async(args, cfg, plan, x, y_ref):
    """``--engine --async``: the ragged requests through the threaded
    ServingFrontend; ``--multi`` co-serves additional frozen packs on the
    same dispatch thread/execution stream."""
    key = jax.random.PRNGKey(1)
    models = {cfg.name: (plan, list(x))}
    for arch in (a for a in (args.multi or "").split(",") if a):
        if arch not in MLPS:
            raise SystemExit(f"--multi: unknown paper MLP {arch!r} "
                             f"(have {sorted(MLPS)})")
        if MLPS[arch].name in models:
            raise SystemExit(f"--multi: {arch!r} duplicates --arch or an "
                             "earlier --multi entry")
        mcfg = MLPS[arch]
        mpack = _freeze_mlp_pack(mcfg, seed=1)
        key, sub = jax.random.split(key)
        mx = jax.random.normal(sub, (args.batch, mcfg.d_in), jnp.float32)
        # co-served packs honor the same flags as the primary plan — the
        # per-model latency lines are only comparable if every model runs
        # the requested configuration.
        mplan = serving.build_plan(
            mpack,
            act_dtype="int8" if args.int8 else "float32",
            double_buffer=args.double_buffer,
            calib_x=mx if args.int8 else None,
            **args._mode_kwargs)
        models[mcfg.name] = (mplan, list(mx))

    names = list(models)
    tiers = _per_model(args.tier, "--tier", names, serving.resolve_tier)
    delays = _per_model(args.max_delay, "--max-delay", names,
                        lambda v: float(v) / 1e3)    # flag is in ms

    # warm every model's request path untimed (compile is not a serving
    # number), then serve all models' ragged rows through one frontend.
    for mplan, rows in models.values():
        jax.block_until_ready(serving.MicroBatcher(mplan).serve(rows)[-1])
    cache = None
    if args.max_hot_models is not None or args.hot_bytes is not None:
        cache = serving.PackCache(max_hot=args.max_hot_models,
                                  hot_bytes=args.hot_bytes)
        print(f"pack cache: hot budget "
              f"{args.max_hot_models if args.max_hot_models else '∞'} "
              f"models / "
              f"{args.hot_bytes if args.hot_bytes else '∞'} bytes — "
              "models registered compressed, decoded on first traffic")
    integrity = True if args.verify_launch else None
    frontend = serving.ServingFrontend(
        cache=cache, streams=args.streams,
        scrub_interval_s=(None if args.scrub_interval is None
                          else args.scrub_interval / 1e3))
    if args.verify_launch or args.scrub_interval is not None:
        print("integrity: "
              + ("per-launch checksum verification + output screen"
                 if args.verify_launch else "no launch guard")
              + (f", scrubber every {args.scrub_interval:.1f} ms"
                 if args.scrub_interval is not None else ""))
    if args.streams > 1:
        devs = [d if d is not None else "<default>"
                for d in frontend._devices]
        print(f"streams: {args.streams} replicated execution streams "
              f"(devices {devs})")
    for name, (mplan, mx_) in models.items():
        wrap = None
        if args.inject_fault > 0 or args.flip_rate > 0:
            def wrap(p):
                return serving.FaultInjector(p, rate=args.inject_fault,
                                             flip_rate=args.flip_rate)
        if cache is not None:
            # compressed-tier registration: the frontend holds the cold
            # pack; the resolved plan lives (and churns) under the LRU.
            # The injector (if any) wraps the cache handle and the guard
            # wraps the injector, so injected corruption is detected by
            # the guard and recovered from the verified cold tier.
            frontend.register_pack(
                name, mplan.pack,
                plan_kwargs={
                    **args._mode_kwargs,
                    "act_dtype": "int8" if args.int8 else "float32",
                    "double_buffer": args.double_buffer,
                    "calib": ({"act_scales": list(mplan.act_scales)}
                              if mplan.act_scales is not None else None),
                },
                wrap=wrap, integrity=integrity,
                tier=tiers[name], max_delay=delays[name],
                max_queued_rows=args.max_queued)
            continue
        target = mplan if wrap is None else wrap(mplan)
        frontend.register(name, target, tier=tiers[name],
                          max_delay=delays[name],
                          max_queued_rows=args.max_queued,
                          integrity=integrity)
        if tiers[name] is not None or delays[name] is not None:
            b = frontend.registry.batcher(name)
            print(f"model [{name}]: tier {b.tier.name}, max_delay "
                  f"{b.max_delay * 1e3:.2f} ms"
                  + (f", queue bound {args.max_queued} rows"
                     if args.max_queued else ""))
    t0 = time.time()
    served, rejected = [], []
    with frontend:
        futs = [(name, i, frontend.submit(name, row))
                for name, (_, rows) in models.items()
                for i, row in enumerate(rows)]
        for name, i, f in futs:
            try:
                served.append((name, i, f.result(60.0)))
            except serving.Rejected as rej:
                rejected.append((name, i, rej.reason))
            except serving.InjectedFault as exc:
                # quarantined model under --inject-fault: its futures
                # carry the injected root cause instead of hanging.
                rejected.append((name, i, f"fault: {exc}"))
            except serving.IntegrityError as exc:
                # corruption that could not be recovered (no cold tier,
                # or the cold copy failed too): typed root cause.
                rejected.append((name, i, f"corrupted: {exc}"))
    dt = time.time() - t0
    n = len(served)
    for name in models:
        lats = [s.latency * 1e3 for m, _, s in served if m == name]
        st = frontend.stats["by_model"][name]
        line = (f"async frontend [{name}]: {st['requests']} requests in "
                f"{st['launches']} launches")
        if lats:
            line += (f", latency mean {np.mean(lats):.2f} ms / p95 "
                     f"{np.percentile(lats, 95):.2f} ms")
        if st["rejected"]:
            line += f", {st['rejected']} rejected"
        if st["quarantined"]:
            line += ", QUARANTINED"
        print(line)
    print(f"async frontend: {n} served / {len(rejected)} rejected across "
          f"{len(models)} model(s) in {dt*1e3:.2f} ms total "
          f"({n/max(dt, 1e-12):.0f} samples/s, "
          f"{frontend.stats['launches']} launches)")
    if args.streams > 1:
        for i, ss in enumerate(frontend.stats["streams"]):
            print(f"stream {i}: {ss['launches']} launches, "
                  f"{ss['busy_s'] * 1e3:.1f} ms busy"
                  + (", QUARANTINED" if ss["quarantined"] else ""))
    if args.inject_fault > 0 or rejected:
        fs = frontend.stats
        print(f"degradation: {fs['launch_failures']} launch failures, "
              f"{fs['retries']} retries, {fs['fallbacks']} chain "
              f"fallbacks, quarantined {fs['quarantined'] or 'none'}")
    if args.flip_rate > 0 or args.verify_launch \
            or args.scrub_interval is not None:
        it = frontend.stats["integrity"]
        sc = frontend.stats["scrub"]
        rec = (f", recovery p95 "
               f"{np.percentile(it['recovery_s'], 95) * 1e3:.2f} ms"
               if it["recovery_s"] else "")
        print(f"integrity: {it['detected']} corruptions detected, "
              f"{it['recovered']} recovered from cold tier{rec}; "
              f"scrubber {sc['cycles']} cycles / {sc['checked']} checks "
              f"({sc['deferred']} busy deferrals)")
    if cache is not None:
        d = cache.describe()
        print(f"pack cache: {d['resolves']} resolves / {d['hits']} hits "
              f"/ {d['evictions']} evictions; resident "
              f"{d['resident_bytes']} B (high water "
              f"{d['resident_high_water']} B), cold tier "
              f"{d['cold_bytes']} B for {d['models']} models "
              f"({d['fp32_bytes'] / max(d['cold_bytes'], 1):.1f}x vs "
              "fp32)")
    # validate whatever completed for the primary model row-by-row (under
    # --inject-fault/--max-queued some rows may be typed rejections).
    done = {i: s for m, i, s in served if m == cfg.name}
    if done:
        got = np.concatenate([np.asarray(done[i].y) for i in sorted(done)])
        ref = np.asarray(y_ref)[sorted(done)]
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


def serve_lm_engine(args, cfg, frozen, prompt, gen_ref):
    """``--engine`` on an LM arch: the same batch through the servable-
    program path — an :class:`~repro.serving.lm.LMProgram` registered in
    a ``ServingFrontend``, every sequence prefilled, then lockstep decode
    steps submitted as wire rows (each decode flush reaches the FFN as an
    ``m = n_seqs`` weight-stationary bucket)."""
    from ..serving.lm import LMProgram

    b, s, new = args.batch, args.prompt_len, args.max_new
    max_bucket = 1 << (max(s, b, 8) - 1).bit_length()
    prog = LMProgram(frozen, cfg, max_prompt=s, max_new=new,
                     max_bucket=max_bucket)
    direct = prog.generate(np.asarray(prompt), new)

    sids = list(range(1000, 1000 + b))
    toks = []
    t0 = time.time()
    frontend = serving.ServingFrontend()
    with frontend:
        frontend.register(cfg.name, prog, max_delay=1e-3)
        futs = [frontend.submit(
                    cfg.name,
                    prog.encode_prefill(sid, np.asarray(prompt)[i])[None])
                for i, sid in enumerate(sids)]
        toks.append([int(f.result(60.0).y[0, 0]) for f in futs])
        for _ in range(new - 1):
            futs = [frontend.submit(cfg.name,
                                    prog.encode_decode(sid)[None])
                    for sid in sids]
            toks.append([int(f.result(60.0).y[0, 0]) for f in futs])
    dt = time.time() - t0
    for sid in sids:
        prog.release(sid)
    engine = np.asarray(toks, np.int64).T
    if not np.array_equal(engine, direct):
        raise AssertionError(
            "engine decode diverged from LMProgram.generate")
    st = frontend.stats
    match = np.array_equal(engine, np.asarray(gen_ref, np.int64))
    print(f"engine (LM program): {b} seqs x {new} tokens in "
          f"{st['launches']} launches, {dt*1e3:.1f} ms total; decode "
          f"bit-identical to the direct generate loop"
          + ("" if match else
             " (jitted baseline tokens differ — accumulation order)"))
    print("program schedules:", prog.describe()["ffn_schedules"])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--iters", type=int, default=10,
                    help="timed iterations (MLP serving path)")
    ap.add_argument("--fused", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="MLP path: whole-stack megakernel vs per-layer")
    ap.add_argument("--int8", action="store_true",
                    help="MLP path: int8 inter-layer activations (§VI-C)")
    ap.add_argument("--double-buffer", action="store_true",
                    help="MLP path: pipelined two-row-group megakernel")
    ap.add_argument("--engine", action="store_true",
                    help="MLP path: also serve the batch as ragged "
                         "single-row requests through the micro-batcher")
    ap.add_argument("--async", dest="async_frontend", action="store_true",
                    help="with --engine: drive the ragged requests "
                         "through the threaded ServingFrontend (real "
                         "clock, futures) instead of the inline flush")
    ap.add_argument("--multi", default=None, metavar="ARCH[,ARCH...]",
                    help="with --engine --async: co-serve additional "
                         "frozen paper-MLP packs from the same frontend "
                         "(one execution stream, deadline-FIFO across "
                         "models)")
    ap.add_argument("--tier", default=None, metavar="TIER[,TIER...]",
                    help="with --engine --async: per-model SLO tier "
                         f"({'|'.join(sorted(serving.TIERS))}); one value "
                         "broadcasts, a comma-separated list aligns to "
                         "[--arch] + --multi.  Enables deadline-based "
                         "admission control for that model")
    ap.add_argument("--max-delay", default=None, metavar="MS[,MS...]",
                    help="with --engine --async: per-model coalescing "
                         "budget in ms (same alignment as --tier); "
                         "overrides the tier's budget")
    ap.add_argument("--max-queued", type=int, default=None, metavar="ROWS",
                    help="with --engine --async: bound every model's "
                         "queue; overflow is a typed serving.Rejected")
    ap.add_argument("--inject-fault", type=float, default=0.0,
                    metavar="RATE",
                    help="with --engine --async: wrap every plan in a "
                         "FaultInjector failing launches at RATE to "
                         "exercise the retry/fallback/quarantine ladder "
                         "(composes with --max-hot-models/--hot-bytes: "
                         "the injector wraps the cache handle)")
    ap.add_argument("--flip-rate", type=float, default=0.0,
                    metavar="RATE",
                    help="with --engine --async: FaultInjector bit-flip "
                         "corruption of live plan operands at RATE per "
                         "launch; requires --verify-launch (detection) "
                         "and, for transparent recovery, the pack cache "
                         "flags (cold-tier re-decode)")
    ap.add_argument("--verify-launch", action="store_true",
                    help="with --engine --async: wrap every model in a "
                         "GuardedPlan — per-launch operand checksum "
                         "verification + NaN/Inf output screen")
    ap.add_argument("--scrub-interval", type=float, default=None,
                    metavar="MS",
                    help="with --engine --async: background integrity "
                         "scrubber cadence in ms (idle-aware; verifies "
                         "cold payload checksums and resident guarded "
                         "plans)")
    ap.add_argument("--max-hot-models", type=int, default=None,
                    metavar="N",
                    help="with --engine --async: register models by "
                         "compressed pack through a serving.PackCache "
                         "and keep at most N resolved plans resident "
                         "(LRU; evicted models re-resolve on next "
                         "traffic, bit-identically)")
    ap.add_argument("--hot-bytes", type=int, default=None, metavar="BYTES",
                    help="with --engine --async: byte budget for the "
                         "pack cache's resident decoded plans (combines "
                         "with --max-hot-models)")
    ap.add_argument("--streams", type=int, default=1, metavar="N",
                    help="with --engine --async: N replicated execution "
                         "streams (one per device on a multi-device "
                         "host; thread-only on a single device) with "
                         "join-shortest-estimated-work dispatch")
    ap.add_argument("--shard", action="store_true",
                    help="MLP path: column-shard the megakernel plan "
                         "over the host's ('data','model') mesh "
                         "(launch.mesh.fit_mesh) — wide layers split "
                         "their output features per device, indivisible "
                         "widths replicate")
    args = ap.parse_args(argv)
    if args.streams < 1:
        raise SystemExit(f"--streams must be >= 1, got {args.streams}")
    if args.streams > 1 and not args.async_frontend:
        raise SystemExit("--streams applies to the async frontend: add "
                         "--engine --async")
    if args.shard and args.arch not in MLPS:
        raise SystemExit("--shard applies to the paper-MLP serving path "
                         f"(--arch one of {sorted(MLPS)})")
    if (args.tier or args.max_delay or args.max_queued is not None
            or args.inject_fault) and not args.async_frontend:
        raise SystemExit("--tier/--max-delay/--max-queued/--inject-fault "
                         "apply to the async frontend: add --engine --async")
    if (args.max_hot_models is not None or args.hot_bytes is not None):
        if not args.async_frontend:
            raise SystemExit("--max-hot-models/--hot-bytes apply to the "
                             "async frontend: add --engine --async")
    if (args.flip_rate > 0 or args.scrub_interval is not None
            or args.verify_launch) and not args.async_frontend:
        raise SystemExit("--flip-rate/--scrub-interval/--verify-launch "
                         "apply to the async frontend: add --engine "
                         "--async")
    if args.flip_rate > 0 and not args.verify_launch:
        raise SystemExit("--flip-rate corrupts live weights; add "
                         "--verify-launch so the corruption is caught "
                         "(and, with the pack cache flags, recovered)")
    if args.multi and not (args.engine and args.async_frontend):
        raise SystemExit("--multi requires --engine --async")
    if args.async_frontend and not args.engine:
        raise SystemExit("--async requires --engine")

    if args.arch in MLPS:
        return serve_mlp(args)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if cfg.family == "audio":
        raise SystemExit("use examples/serve_whisper-style driving for enc-dec")

    key = jax.random.PRNGKey(0)
    params = T.lm_init(key, cfg)
    qstate = qat.build_qstate(params)
    frozen = qat.freeze_tree(params, qstate, cfg.lam)
    ctx = QuantCtx(quant=False, compute_dtype=jnp.float32)

    b, s, new = args.batch, args.prompt_len, args.max_new
    prompt = jax.random.randint(key, (b, s), 0, cfg.vocab)
    total = s + new

    @jax.jit
    def prefill(params, tokens):
        cache = T.init_cache(cfg, b, total, dtype=jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        logits, cache, _ = T.lm_apply(params, 0, tokens, ctx, cfg,
                                      positions=pos, cache=cache)
        nxt = jnp.argmax(logits[:, -1:, :cfg.vocab], -1).astype(jnp.int32)
        return nxt, cache

    @jax.jit
    def decode(params, tok, pos, cache):
        logits, cache, _ = T.lm_apply(params, 0, tok, ctx, cfg,
                                      positions=pos, cache=cache)
        nxt = jnp.argmax(logits[:, -1:, :cfg.vocab], -1).astype(jnp.int32)
        return nxt, cache

    t0 = time.time()
    tok, cache = prefill(frozen, prompt)
    tok.block_until_ready()
    t_prefill = time.time() - t0

    out = [tok]
    t0 = time.time()
    for t in range(new - 1):
        pos = jnp.full((b, 1), s + t, jnp.int32)
        tok, cache = decode(frozen, tok, pos, cache)
        out.append(tok)
    jax.block_until_ready(out[-1])
    t_dec = time.time() - t0

    gen = np.asarray(jnp.concatenate(out, axis=1))
    print(f"prefill: {t_prefill*1e3:.1f} ms  decode: "
          f"{t_dec/(new-1)*1e3 if new > 1 else 0:.1f} ms/token "
          f"({b} sequences)")
    print("generated ids[0]:", gen[0].tolist())
    if args.engine:
        try:
            serve_lm_engine(args, cfg, frozen, prompt, gen)
        except ValueError as e:
            raise SystemExit(f"--engine: {e}")
    return gen


if __name__ == "__main__":
    from . import compile_cache
    print(f"compile cache: {compile_cache.enable()}")
    main()
