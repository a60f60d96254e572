"""One benchmark run: set-up, the measured window, the check, the result.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--sweep <rate>,<rate>,...]

A run loads ``workloads/<cell>.json`` and its ``configs/<config>.json``,
makes the model from the seed (``models/<family>.py``), builds the plan
with the configuration's committed block bindings
(``bindings/<config>.json``; no sweep), lets ``traffic/<kind>.py``
warm up exactly the shapes its traffic uses (a block bound by the plan
build or the warm-up that the file lacks ends the run), measures for
``--seconds``,
checks what the timed path produced against the plain reference, and
prints one JSON line.  With ``--trace 1`` the window runs under the
profiler and the line carries the per-layer metrics instead of the
end-to-end ones; the readers find the trace's reduction, the program's
``serving.*`` spans among it (``harness/spans.py``).  ``--sweep`` runs
the window once per offered rate (open-loop traffic only), prints one
line per rate and no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, List, Optional

import numpy as np

from . import loader, spans, trace as tracemod
from .meter import CompileMeter

RECORD = "python3 chipbench/tools/record_bindings.py --config"


def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="",
                    help="comma-separated offered rates (requests/s)")
    return ap.parse_args(argv)


class Readings:
    """What the metric readers read: the window's numbers, the trace's
    reduction (``trace``: ``harness.trace.reduce``'s, with every device
    op's seconds under ``op_s``, and the program's own ``spans``, as
    ``harness.spans.reduce`` gives them) and the trace file it came from,
    the set-up split, and the cell's model family, shapes and device."""

    def __init__(self, run: "Run", window: dict, reduced: Optional[dict],
                 xspace: Optional[str] = None):
        self.run = run
        self.window = window
        self.trace = reduced
        self.xspace = xspace
        self.setup_s = run.setup_s
        self.family = run.family
        self.shapes = run.shapes
        self.act_dtype = run.config["act_dtype"]
        self.device_kind = run.device_kind
        self.chips = run.chips


class Run:
    """The state of one run, handed to the traffic generator."""

    def __init__(self, args, workload: dict, config: dict, t_start: float):
        self.args = args
        self.seed = args.seed
        self.workload = workload
        self.params = workload.get("params", {})
        self.config = config
        self.chips = int(workload["chips"])
        self.family = loader.family(config["family"])
        self.shapes = self.family.shapes(config)
        self.t_start = t_start
        self.split = {}
        self.setup_s = None
        self.model = None
        self.plan = None
        self.device_kind = None

    def mark(self, what: str, since: float) -> float:
        now = time.perf_counter()
        self.split[what] = now - since
        return now

    def rng(self, stream: int):
        return self.family.seed_rng(self.seed, stream)


def _log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        try:
            peaks.append(int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0)))
        except Exception:              # noqa: BLE001 — not every backend
            peaks.append(0)
    return max(peaks) if peaks else 0


def use_bindings(tmp: str, path: str) -> None:
    """Point the block autotuner at a copy in ``tmp`` of the bindings file
    ``path`` (the program writes into the file it is given) and turn on
    JAX's persistent compile cache at the program's fixed place."""
    from repro.kernels import autotune
    from repro.launch import compile_cache
    copy = os.path.join(tmp, "autotune.json")
    shutil.copyfile(path, copy)
    os.environ[autotune.ENV_CACHE] = copy
    autotune.clear_memory_cache()
    compile_cache.enable()


def execute(argv: Optional[List[str]] = None, *, require_chip: bool = True,
            workload: Optional[dict] = None, config: Optional[dict] = None,
            bench: Optional[dict] = None, t_start: Optional[float] = None,
            on_readings: Optional[Callable[[Readings], None]] = None
            ) -> int:
    """Run once; returns the exit code.  A configuration read from its
    file runs on its committed bindings, and a run without them exits 1.
    Tests only: ``require_chip=False`` skips the look for a TPU, and the
    workload, config and metric list are taken as given (a config given
    has no bindings file; its plan binds as the autotuner finds).
    ``on_readings`` (tools) is called with the run's readings once the
    metrics are read; the trace file they name lasts until it returns."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    wl = workload or loader.workload(args.workload)
    cfg = config or loader.config(wl["config"])
    spec = bench or loader.spec()
    run = Run(args, wl, cfg, t_start)

    import jax
    devices = jax.devices()
    if require_chip:
        if jax.default_backend() != "tpu":
            _log(f"chipbench: needs a TPU; JAX's backend is "
                 f"{jax.default_backend()!r}")
            return 2
        if len(devices) < run.chips:
            _log(f"chipbench: {args.workload} needs {run.chips} chips; JAX "
                 f"has {len(devices)}")
            return 2
    used = devices[:run.chips]
    run.device_kind = used[0].device_kind

    pinned = None if config is not None else \
        loader.bindings_path(cfg["name"])
    if pinned is not None and not os.path.isfile(pinned):
        _log(f"chipbench: configuration {cfg['name']!r} has no block "
             f"bindings ({os.path.relpath(pinned, loader.ROOT)}); record "
             f"them on the chip with {RECORD} {cfg['name']}")
        return 1
    tmp = tempfile.mkdtemp(prefix="chipbench-")
    try:
        if pinned is not None:
            use_bindings(tmp, pinned)
        meter = CompileMeter()
        return _run(run, spec, used, meter, tmp, pinned, on_readings)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _bindings_held(run: Run, pinned: Optional[str], what: str) -> bool:
    """Whether the autotuner's copy still equals the pinned bindings file
    (always, for a run without one); if ``what`` bound a block the file
    lacks, says which file and how to record it."""
    from repro.kernels import autotune
    if pinned is None:
        return True
    with open(pinned) as a, open(autotune.cache_path()) as b:
        if json.load(a) == json.load(b):
            return True
    _log(f"chipbench: {what} swept block bindings that "
         f"{os.path.relpath(pinned, loader.ROOT)} lacks; record them with "
         f"{RECORD} {run.config['name']}")
    return False


def _run(run: Run, spec: dict, used, meter, tmp: str, pinned: Optional[str],
         on_readings: Optional[Callable[[Readings], None]]) -> int:
    import jax
    args = run.args
    traffic = loader.traffic(run.workload["traffic"]).Traffic(run)

    t = time.perf_counter()
    run.split["start"] = t - run.t_start
    run.model = run.family.Model(run.config, run.seed)
    jax.block_until_ready(run.model.device_arrays())
    t = run.mark("weights", t)
    run.plan = run.model.plan()
    t = run.mark("plan", t)
    if not _bindings_held(run, pinned, "the plan build"):
        return 1
    traffic.setup()
    t = run.mark("warmup", t)
    if not _bindings_held(run, pinned, "the warm-up"):
        traffic.close()
        return 1
    # what set-up made lives as long as the process: keep it out of the
    # collector's full passes, which would otherwise stall the window
    gc.collect()
    gc.freeze()
    run.setup_s = time.perf_counter() - run.t_start
    before = meter.snapshot()
    _log(f"set-up {run.setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in run.split.items())
        + f"; {before['compiles']} compiles {before['compile_s']:.3f} s, "
        f"persistent-cache hits {before['cache_hits']}, misses "
        f"{before['cache_misses']}")
    describe = run.plan.describe()
    _log("plan: " + json.dumps({k: describe[k]
                                for k in run.family.PLAN_KEYS}))

    if args.sweep:
        for rate in [float(r) for r in args.sweep.split(",") if r]:
            w = traffic.window(args.seconds, rate=rate)
            print(json.dumps(traffic.summary(w)), flush=True)
        traffic.close()
        return 0

    log_dir = os.path.join(tmp, "trace")
    if args.trace:
        jax.profiler.start_trace(log_dir, profiler_options=_trace_options())
    try:
        window = traffic.window(args.seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    after = meter.snapshot()
    in_window = after["compiles"] - before["compiles"]
    _log(f"window: {json.dumps(traffic.summary(window))}; compiles inside "
         f"the window {in_window}")
    memory_peak = _memory_peak(used)
    traffic.close()

    reduced = xspace = None
    if args.trace:
        t = time.perf_counter()
        xspace = tracemod.find_xspace(log_dir)
        loaded = tracemod.load(xspace)
        ids = [d.id for d in used]
        reduced = tracemod.reduce(loaded, devices=ids)
        reduced["spans"] = spans.reduce(loaded, devices=ids)
        del loaded
        _log(f"trace: read in {time.perf_counter() - t:.3f} s; window "
             f"{reduced['window_s']:.6f} s, busy {reduced['busy_s']}, "
             f"kernel {reduced['kernel_s']}")

    t = time.perf_counter()
    checks = compare(run, traffic.sample(window))
    _log(f"check: {time.perf_counter() - t:.3f} s")
    limits = run.workload["limits"]
    correct = window["failed_check"] == 0 and all(
        math.isfinite(v) and v <= limits[k] for k, v in checks.items())

    readings = Readings(run, window, reduced, xspace)
    metrics = {}
    for entry in loader.metrics_for(spec, args.workload, bool(args.trace)):
        value = loader.metric_reader(entry["name"]).read(readings)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    if on_readings is not None:
        on_readings(readings)
    dev = used[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": device}
    if reduced is not None:
        device["busy_s"] = sum(reduced["busy_s"]) / len(reduced["busy_s"])
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in checks.items()}
    if window["failed_check"]:
        _log(f"check: {window['failed_check']} answers never came")
    for k, v in checks.items():
        _log(f"check {k}: {v!r} (limit {limits[k]!r})"
             f"{'' if v <= limits[k] else ' FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


NUMBERS = ("max_rel_err", "miss_share")


def compare(run: Run, pairs, control: Optional[str] = None,
            names: Optional[List[str]] = None) -> dict:
    """The numbers compared (the cell's ``limits``, or ``names``) over
    every (rows, served logits) pair, or with ``control`` of that control
    put in the program's place: ``max_rel_err``, the widest gap from the
    reference over its largest logit, and ``miss_share``, the share of
    rows that miss it (``models/<family>.miss_count``)."""
    names = list(run.workload["limits"] if names is None else names)
    worst, missed, rows = 0.0 if pairs else math.inf, 0.0, 0
    d_out = run.model.d_out
    for x, y in pairs:
        if np.shape(y) != (np.shape(x)[0], d_out):
            worst, missed = math.inf, missed + np.shape(x)[0]
        else:
            ref = run.model.reference(x)
            got = y if control is None else run.model.control(x, control)
            worst = max(worst, run.family.max_rel_err(got, ref))
            missed += run.family.miss_count(got, ref)
        rows += np.shape(x)[0]
    found = {"max_rel_err": worst,
             "miss_share": missed / rows if rows else math.inf}
    return {k: found[k] for k in names}


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts
