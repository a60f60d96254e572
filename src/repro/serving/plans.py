"""Execution plans: serve-time dispatch resolved once, at freeze time.

Before this module, every serving entry point re-decided its execution
strategy per call by threading mode keywords (``fused=``, ``int8=``,
``double_buffer=``, ``block_m=``, ``interpret=``) down through
``models/mlp.py`` into ``kernels/ops.py`` — and the launcher, two
benchmarks and the examples each re-implemented the same resolution
slightly differently.  An :class:`ExecutionPlan` captures the whole
decision once per frozen pack:

* **mode** — ``fused`` (megakernel) / ``per_layer`` (chained kernel) /
  ``oracle`` (pure jnp) / ``sharded`` (the column-split multi-device
  program over a ``('data','model')`` mesh — pass ``mesh=``, see
  ``serving.sharded``), with ``auto`` resolving to the fastest
  single-device mode that fits; the VMEM-budget check runs at build
  time, so a stack that cannot fuse is *reported* as ``per_layer``
  instead of silently falling back inside the kernel wrapper on every
  call.
* **activation dtype** — fp32 or the paper's §VI-C int8 inter-layer
  activations; int8 calibration runs once at plan build (a provided calib
  dict, a calibration batch, or a deterministic synthetic batch), never
  per request.
* **block sizes** — the autotuner is consulted once (timed sweep on TPU,
  heuristic in interpret mode) and the tuned ``block_m`` is pinned into
  every entry point.
* **batch buckets** — powers of two up to the tuned ``block_m``.  Each
  bucket resolves to a concrete kernel schedule via **autotuner v2**
  (``kernels.autotune.get_schedule_config``): on a real backend a timed
  sweep over every *eligible* ``(schedule, block_m)`` candidate —
  batch-tiled, double-buffered, weight-stationary, decode-amortized
  streaming — binds the bucket to its *measured* winner; in interpret
  mode a dataflow prior answers (ws for the ≤``WS_BUCKET_ROWS`` latency
  buckets, db where requested and engageable, batch-tiled otherwise,
  stream when the whole stack busts the batch-tiled VMEM budget), since
  timing the interpreter is meaningless.  The measured ws↔batch-tiled
  crossover row count is persisted with the cache and replaces the
  ``WS_BUCKET_ROWS`` constant as the prior once it exists
  (``ws_bucket_rows=0`` opts the ws schedule out entirely; an explicit
  positive value caps its eligibility).  ``entry(b)`` returns a
  shape-stable callable per bucket, so serving a stream of ragged batch
  sizes compiles ``len(buckets)`` programs instead of one per distinct
  size.

The micro-batcher (``serving.batcher``) sits on top: it coalesces queued
requests into these buckets so the execution units always see full row
tiles — the runtime half of the paper's throughput story.
"""
from __future__ import annotations

import dataclasses
from typing import (Callable, Dict, List, Optional, Protocol, Sequence,
                    Tuple, runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..kernels import ops as kops
from ..kernels.fantastic4_fused_mlp import (VMEM_BUDGET_BYTES,
                                            fused_mlp_decode_once,
                                            fused_mlp_fits,
                                            stream_mlp_fits, ws_mlp_fits)
from ..kernels import autotune
from ..memo import MISS, IdentityMemo

MODES = ("auto", "fused", "per_layer", "oracle", "sharded")
ACT_DTYPES = ("float32", "int8")
# weight-stationary latency prior: one f32 sublane tile — the dataflow-
# motivated *pre-measurement* answer only.  On a real backend the
# per-bucket timed sweep decides, and the measured ws↔batch-tiled
# crossover persisted in the autotune cache replaces this constant as the
# prior from then on (on the CPU-interpret host the per-layer grid steps
# make ws ~2-3x slower at batch 1, which is exactly why the gate must be
# measured, not assumed).  ``ws_bucket_rows=0`` opts the ws schedule out;
# an explicit positive value caps its eligibility.
WS_BUCKET_ROWS = 8
DEFAULT_MAX_BUCKET = 256
_CALIB_BATCH = 64

# bucket path <-> kernel schedule naming (paths predate autotuner v2 and
# are kept stable for describe()/bench labels).
PATH_BY_SCHEDULE = {"ws": "fused_ws", "batch_tiled": "fused",
                    "db": "fused_db", "stream": "fused_stream"}
SCHEDULE_BY_PATH = {v: k for k, v in PATH_BY_SCHEDULE.items()}


@runtime_checkable
class ServableProgram(Protocol):
    """The contract every serving layer programs against.

    A servable program maps ``(rows, d_in)`` float32 batches to
    ``(rows, d_out)`` outputs through a fixed set of row *buckets*, each
    backed by a shape-stable compiled entry point.  The micro-batcher,
    frontend/registry, pack cache, integrity guard and fault injector all
    depend on exactly this surface — :class:`ExecutionPlan` (a frozen MLP
    pack), ``serving.lm.LMProgram`` (a 4-bit transformer's prefill/decode
    engine), and the ``CachedPlan``/``GuardedPlan``/``FaultInjector``
    proxies are interchangeable implementations.

    Required:

    * ``d_in`` / ``d_out`` — the wire width of one request row.  For
      tensor programs these are the feature dims; programs with their own
      request encoding (e.g. the LM program's token rows) document the
      row layout in ``describe()``.
    * ``bucket_sizes`` — ascending row buckets the program compiles for.
    * ``bucket_for(m)`` — smallest bucket holding ``m`` rows (None when
      ``m`` overflows the largest bucket).
    * ``entry(bucket)`` — shape-stable callable for exactly ``bucket``
      rows.
    * ``run(x)`` — pad-to-bucket convenience wrapper around ``entry``.
    * ``describe()`` — a JSON-able report of what will execute.

    Optional, feature-detected via ``getattr``/``hasattr`` (never
    ``isinstance`` on a concrete class — the acceptance contract of the
    serving hot path):

    * ``rows_per_request`` — fixed row count each request must carry
      (programs with per-row request state, e.g. one row per decode
      sequence); absent/None means any row count.
    * ``warmup(buckets=None)`` — precompile entry points.
    * ``demote_bucket(rows, reason=...)`` — degradation rebind.
    * ``buckets`` / ``schedule_for`` / ``mode_label`` — schedule
      reporting surfaces used by benches and the frontend's degradation
      ladder.
    * ``layers`` — the 4-bit pack layer dicts backing the program (CRC
      verification, bit-flip injection, operand-cache release).
    * ``pack`` / ``act_dtype`` / ``act_scales`` — pack-cache plumbing.
    """

    d_in: int
    d_out: int
    bucket_sizes: Tuple[int, ...]

    def bucket_for(self, m: int) -> Optional[int]: ...

    def entry(self, bucket: int) -> Callable: ...

    def run(self, x): ...

    def describe(self) -> dict: ...


def calibrate_act_scales(pack: dict, x_calib: jax.Array) -> dict:
    """Per-layer activation scales from a calibration batch — the paper's
    8-bit-activation FPGA configuration.  alpha2 of layer i becomes the
    re-quantization scale mapping the ReLU output onto the next layer's
    int8 grid; the next layer's alpha1 absorbs the de-quantization."""
    scales = []
    x = x_calib.astype(jnp.float32)
    for layer in pack["layers"]:
        if layer["shape"][0] % 2:
            # odd K: the pack carries one zero code row — mirror it on x
            x = jnp.pad(x, ((0, 0), (0, 1)))
        y = kops.fantastic4_matmul(
            x, layer["packed"], layer["omega"], bias=layer["bias"],
            alpha1=layer["alpha1"], alpha2=None,
            activation=layer["activation"], use_kernel=False)
        s = jnp.maximum(jnp.max(jnp.abs(y)), 1e-6) / 127.0
        scales.append(float(s))
        x = y
    return {"act_scales": scales}


def _default_calib_x(d_in: int, seed: int = 0) -> jax.Array:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(_CALIB_BATCH, d_in)), jnp.float32)


def _pow2_buckets(max_rows: int) -> Tuple[int, ...]:
    out, b = [], 1
    while b <= max_rows:
        out.append(b)
        b *= 2
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """One resolved (bucket rows → kernel schedule) binding."""
    rows: int
    path: str        # "fused[_ws|_db|_stream]" | "per_layer" | "oracle"
    block_m: Optional[int] = None      # per-bucket tuned tile (fused paths)
    source: str = "mode"     # "sweep" | "heuristic" | "migrated" | "mode"


class ExecutionPlan:
    """Frozen-pack serving plan: mode, blocks, calibration and per-bucket
    entry points resolved once.  Build with :func:`build_plan` (or the
    memoizing :func:`get_plan`).  The reference :class:`ServableProgram`
    implementation — a pure tensor program with no per-request state, so
    ``rows_per_request`` stays None (any row count)."""

    rows_per_request: Optional[int] = None

    def __init__(self, pack: dict, *,
                 mode: str = "auto",
                 act_dtype: str = "float32",
                 double_buffer: bool = False,
                 ws_bucket_rows: Optional[int] = None,
                 calib: Optional[dict] = None,
                 calib_x: Optional[jax.Array] = None,
                 interpret: Optional[bool] = None,
                 block_m: Optional[int] = None,
                 max_bucket: int = DEFAULT_MAX_BUCKET,
                 vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
                 mesh=None):
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        if mode == "sharded" and mesh is None:
            raise ValueError("mode='sharded' requires mesh= (build one "
                             "with launch.mesh.fit_mesh)")
        self.mesh = mesh
        if act_dtype not in ACT_DTYPES:
            raise ValueError(
                f"act_dtype must be one of {ACT_DTYPES}, got {act_dtype!r}")
        self.pack = pack
        self.layers = pack["layers"]
        self.shapes = tuple(tuple(l["shape"]) for l in self.layers)
        self.d_in = self.shapes[0][0]
        self.d_out = self.shapes[-1][1]
        self.requested_mode = mode
        self.act_dtype = act_dtype
        self.requested_double_buffer = double_buffer
        self.interpret = (kops.default_interpret()
                          if interpret is None else interpret)
        self.vmem_budget_bytes = vmem_budget_bytes
        self.notes: List[str] = []
        self._stack_extra = "stack" + "x".join(str(n) for _, n in
                                               self.shapes)
        self._backend_key = "interpret" if self.interpret else \
            jax.default_backend()

        # ws gating: an explicit value is both the eligibility ceiling and
        # the prior (0 = opt out entirely); None leaves eligibility to the
        # VMEM fit and takes the prior from the measured crossover when one
        # exists for this backend, else the WS_BUCKET_ROWS constant.
        self.ws_bucket_rows = ws_bucket_rows
        if ws_bucket_rows is not None:
            self.ws_eligible_rows: Optional[int] = ws_bucket_rows
            self.ws_prior_rows = ws_bucket_rows
            self.ws_prior_source = "explicit"
        elif mode in ("auto", "fused"):
            self.ws_eligible_rows = None
            measured = autotune.get_ws_crossover(
                self.d_in, self.d_out, backend=self._backend_key,
                act_dtype=act_dtype, stack=self._stack_extra)
            if measured is not None:
                self.ws_prior_rows = measured
                self.ws_prior_source = "measured"
            else:
                self.ws_prior_rows = WS_BUCKET_ROWS
                self.ws_prior_source = "constant"
        else:
            self.ws_eligible_rows = 0
            self.ws_prior_rows = 0
            self.ws_prior_source = "mode"

        # ---- int8 calibration: once, at build time
        self.act_scales: Optional[List[float]] = None
        if act_dtype == "int8":
            if calib is not None:
                self.act_scales = list(calib["act_scales"])
            else:
                if calib_x is None:
                    calib_x = _default_calib_x(self.d_in)
                    self.notes.append(
                        "int8 calibration ran on a synthetic batch "
                        f"({_CALIB_BATCH}x{self.d_in}); pass calib=/calib_x= "
                        "for task-realistic scales")
                self.act_scales = list(
                    calibrate_act_scales(pack, calib_x)["act_scales"])

        # ---- sharded: the column-split multi-device program
        # (serving.sharded), built once here — operands device_put under
        # the partition rules, one jitted program per batch shape.
        self._sharded = None
        if mode == "sharded":
            from .sharded import ShardedStack
            self._sharded = ShardedStack(
                pack, mesh, act_dtype=act_dtype,
                act_scales=self.act_scales, interpret=self.interpret)

        # ---- mode resolution: the VMEM-fit decision happens HERE, not
        # per call inside the kernel wrapper, so callers can report the
        # path that will actually execute before running anything.  A
        # stack too big for the batch-tiled (whole-stack-resident)
        # megakernel can still fuse through the layer-streamed schedules
        # (stream/ws hold one layer per grid step).
        self._stack_fits = fused_mlp_fits(
            self.shapes, block_m=block_m or 256,
            budget_bytes=vmem_budget_bytes, act_dtype=act_dtype)
        self._stack_fits_db = fused_mlp_fits(
            self.shapes, block_m=block_m or 256,
            budget_bytes=vmem_budget_bytes, act_dtype=act_dtype,
            double_buffer=True)
        # gate at the minimal (8-row) tile: "some stream configuration
        # serves max_bucket rows" — per-bucket binding then picks (and
        # fit-guards) the actual tile.
        stream_ok = stream_mlp_fits(
            self.shapes, rows=max_bucket, block_m=8,
            budget_bytes=vmem_budget_bytes, act_dtype=act_dtype)
        if mode == "auto":
            mode = "fused" if (self._stack_fits or stream_ok) \
                else "per_layer"
        if mode == "fused" and not self._stack_fits:
            if stream_ok:
                self.notes.append(
                    "stack exceeds the whole-stack (batch-tiled) "
                    f"megakernel VMEM budget ({vmem_budget_bytes} B): "
                    "only the layer-streamed schedules (stream/ws) are "
                    "eligible")
            else:
                self.notes.append(
                    "stack exceeds the fused-megakernel VMEM budget "
                    f"({vmem_budget_bytes} B): resolved to per_layer")
                mode = "per_layer"
        self.resolved_mode = mode

        # ---- blocks: the plan-wide block_m (largest bucket / overflow
        # batches).  On a real backend the consultation must carry a
        # measure closure: answering from the heuristic would persist a
        # non-sweep entry under the real backend's cache key and
        # permanently mask the timed sweep (the autotuner's own contract).
        self.block_m = block_m
        self.block_source = "explicit" if block_m is not None else None
        if mode == "fused" and block_m is None:
            if self._stack_fits:
                def _measure(cfg: autotune.BlockConfig) -> float:
                    xm = jnp.zeros((max_bucket, self.d_in), jnp.float32)
                    return kops._timeit(lambda: kops.fantastic4_mlp_fused(
                        xm, self.layers, use_kernel=True,
                        interpret=self.interpret, block_m=cfg.block_m,
                        act_dtype=act_dtype, act_scales=self.act_scales,
                        vmem_budget_bytes=vmem_budget_bytes))

                # a sweep candidate that fails to compile is reported in
                # the notes with its error, here and per bucket below
                with autotune.collect_failures(self.notes):
                    cfg = autotune.get_block_config(
                        max_bucket, self.d_in, self.d_out,
                        dtype="float32", fused=True,
                        backend="interpret" if self.interpret else None,
                        act_dtype=act_dtype,
                        extra=self._stack_extra,
                        measure=None if self.interpret else _measure)
                self.block_m = cfg.block_m
                self.block_source = cfg.source
            else:
                # batch-tiled ineligible: nothing to sweep at the stack
                # level; per-bucket stream tiles are tuned below.
                self.block_m = autotune.heuristic_blocks(
                    max_bucket, self.d_in, self.d_out, fused=True,
                    backend=self._backend_key).block_m
                self.block_source = "heuristic"

        # ---- buckets: powers of two up to min(block_m, max_bucket),
        # each bound to its own (schedule, block_m) by autotuner v2.
        top = max_bucket
        if mode == "fused" and self.block_m:
            top = min(top, max(self.block_m, 1))
        self.bucket_sizes = _pow2_buckets(max(top, 1))
        self.buckets: Dict[int, BucketPlan] = {}
        self.ws_crossover_rows: Optional[int] = None
        if mode in ("per_layer", "oracle", "sharded"):
            for b in self.bucket_sizes:
                self.buckets[b] = BucketPlan(b, mode)
            self.default_path = mode
        else:
            with autotune.collect_failures(self.notes):
                for b in self.bucket_sizes:
                    self.buckets[b] = self._bind_bucket(b, max_bucket)
            # overflow batches (past the largest bucket) run at exact size:
            # batch-tiled (double-buffered when requested and it fits) or
            # the per-layer chain when the whole stack can't reside.
            if self._stack_fits_db and double_buffer:
                self.default_path = "fused_db"
            elif self._stack_fits:
                self.default_path = "fused"
            else:
                self.default_path = "per_layer"
            ws_won = [b for b, p in self.buckets.items()
                      if p.path == "fused_ws"]
            self.ws_crossover_rows = max(ws_won) if ws_won else 0
            fused_srcs = [p.source for p in self.buckets.values()
                          if p.path.startswith("fused")]
            if (not self.interpret and fused_srcs
                    and self.ws_eligible_rows is None
                    and all(s == "sweep" for s in fused_srcs)):
                # every bucket measured with ws fully eligible: persist
                # the ws<->batch-tiled crossover so future plans (and
                # hosts sharing the cache) start from the measurement,
                # not the constant.  An opt-out/capped plan must NOT
                # record — its "crossover" reflects the caller's
                # restriction, not a measurement.
                autotune.record_ws_crossover(
                    self.ws_crossover_rows, self.d_in, self.d_out,
                    backend=self._backend_key, act_dtype=act_dtype,
                    stack=self._stack_extra)

        if double_buffer:
            if mode != "fused":
                self.notes.append(
                    "double_buffer requested but resolved mode is "
                    f"{mode}: ignored")
            elif not any(p.path == "fused_db" for p in self.buckets.values()):
                if max(self.bucket_sizes) < 16:
                    self.notes.append(
                        "double_buffer requested but no bucket has a "
                        ">=16-row tile: single-buffered schedule everywhere")
                else:
                    self.notes.append(
                        "double_buffer requested but the per-bucket "
                        "schedule sweep bound other schedules everywhere")
        if (mode == "fused" and self.ws_eligible_rows != 0
                and not any(p.path == "fused_ws"
                            for p in self.buckets.values())):
            if not ws_mlp_fits(self.shapes, rows=1,
                               budget_bytes=vmem_budget_bytes,
                               act_dtype=act_dtype):
                self.notes.append(
                    "weight-stationary latency path unavailable (per-layer "
                    "working set exceeds the VMEM budget)")
            elif self.ws_prior_source == "measured":
                self.notes.append(
                    "weight-stationary schedule measured out (crossover "
                    f"{self.ws_prior_rows} rows): other schedules won "
                    "every bucket")

        self._entries: Dict[int, Callable] = {}
        self._oversize_memo: Dict[int, BucketPlan] = {}
        self._decode_memo: Dict[BucketPlan, str] = {}

    # ------------------------------------------------------------ resolve

    def _eligible_schedules(self, rows: int) -> tuple:
        """Schedules whose VMEM working set fits this bucket, with the ws
        opt-out/ceiling applied — the candidate set the sweep may bind."""
        el = []
        if self._stack_fits:
            el.append("batch_tiled")
            if rows >= 16 and self._stack_fits_db:
                el.append("db")
        if stream_mlp_fits(self.shapes, rows=rows, block_m=8,
                           budget_bytes=self.vmem_budget_bytes,
                           act_dtype=self.act_dtype):
            el.append("stream")
        cap = self.ws_eligible_rows
        if cap != 0 and (cap is None or rows <= cap) and \
                ws_mlp_fits(self.shapes, rows=rows,
                            budget_bytes=self.vmem_budget_bytes,
                            act_dtype=self.act_dtype):
            el.append("ws")
        return tuple(el)

    def _prior_schedule(self, rows: int, eligible: tuple) -> str:
        """Pre-measurement answer: the dataflow-motivated prior (measured
        crossover when the cache has one — see ws_prior_source)."""
        if "ws" in eligible and rows <= self.ws_prior_rows:
            return "ws"
        if "db" in eligible and self.requested_double_buffer:
            return "db"
        if "batch_tiled" in eligible:
            return "batch_tiled"
        return eligible[0]

    def _schedule_fits(self, schedule: str, rows: int, bm: int) -> bool:
        """Does this exact (schedule, block_m) candidate fit VMEM?  The
        sweep must never time a candidate that would silently take the
        per-layer chain fallback inside the kernel wrapper — a chain time
        winning under a fused label is exactly the mislabel the schedule
        bindings exist to prevent."""
        if schedule == "batch_tiled":
            return self._stack_fits
        if schedule == "db":
            return self._stack_fits_db
        if schedule == "ws":
            return ws_mlp_fits(self.shapes, rows=rows,
                               budget_bytes=self.vmem_budget_bytes,
                               act_dtype=self.act_dtype)
        return stream_mlp_fits(self.shapes, rows=rows, block_m=bm,
                               budget_bytes=self.vmem_budget_bytes,
                               act_dtype=self.act_dtype)

    def _schedule_measure(self, rows: int) -> Callable[[str, int], float]:
        xm = jnp.zeros((rows, self.d_in), jnp.float32)

        def measure(schedule: str, bm: int) -> float:
            if not self._schedule_fits(schedule, rows, bm):
                return float("inf")
            return kops._timeit(lambda: kops.fantastic4_mlp_fused(
                xm, self.layers, use_kernel=True, interpret=self.interpret,
                block_m=bm, act_dtype=self.act_dtype,
                act_scales=self.act_scales, schedule=schedule,
                vmem_budget_bytes=self.vmem_budget_bytes))
        return measure

    def _bind_bucket(self, rows: int, max_bucket: int) -> BucketPlan:
        eligible = self._eligible_schedules(rows)
        if not eligible:
            return BucketPlan(rows, "per_layer", source="mode")
        cfg = autotune.get_schedule_config(
            rows, self.d_in, self.d_out,
            schedules=eligible,
            prior=self._prior_schedule(rows, eligible),
            dtype="float32", backend=self._backend_key,
            act_dtype=self.act_dtype, stack=self._stack_extra,
            measure=None if self.interpret else
            self._schedule_measure(rows),
            legacy_m=max_bucket, block_m_hint=self.block_m)
        bm = cfg.block_m
        if cfg.schedule == "stream" and cfg.source != "sweep" and bm:
            # prior/migrated tile was chosen without a fit check: halve
            # until the streaming working set fits, so the binding can
            # never silently execute the chain fallback under its label.
            while bm > 8 and not self._schedule_fits("stream", rows, bm):
                bm //= 2
        return BucketPlan(rows, PATH_BY_SCHEDULE[cfg.schedule],
                          block_m=bm, source=cfg.source)

    def bucket_for(self, m: int) -> Optional[int]:
        """Smallest bucket holding ``m`` rows; None when ``m`` overflows
        the largest bucket (run at exact size via the oversize binding)."""
        for b in self.bucket_sizes:
            if m <= b:
                return b
        return None

    def oversize_binding(self, m: int) -> BucketPlan:
        """Resolved ``(path, block_m)`` for a batch past the largest
        bucket (run at exact size — the fused kernels grid over row
        tiles).  The largest bucket's tuned winner is the closest
        measurement the sweep ever produced for this size class, so
        oversize batches inherit it — fit-guarded at the *actual* row
        count, since the streamed working sets grow with rows.  Routing
        them down a plan-level ``default_path``/``block_m`` instead (the
        pre-fix behavior) executed a schedule no sweep ever bound for
        that size while ``path_for``/``schedule_for``/bench labels
        claimed otherwise."""
        cached = self._oversize_memo.get(m)
        if cached is not None:
            return cached
        bp = self._resolve_oversize(m)
        self._oversize_memo[m] = bp
        return bp

    def _resolve_oversize(self, m: int) -> BucketPlan:
        if self.resolved_mode in ("per_layer", "oracle", "sharded"):
            return BucketPlan(m, self.resolved_mode, source="mode")
        top = self.buckets[max(self.bucket_sizes)]
        if top.path.startswith("fused"):
            sched = SCHEDULE_BY_PATH[top.path]
            bm = top.block_m or self.block_m or 8
            if sched == "stream":
                # the streamed working set scales with block_m: shrink the
                # inherited tile until it fits at m rows before giving up.
                while bm > 8 and not self._schedule_fits(sched, m, bm):
                    bm //= 2
            if self._schedule_fits(sched, m, bm):
                return BucketPlan(m, top.path, block_m=bm,
                                  source=top.source)
        # top bucket's winner does not scale to m rows: the whole-stack
        # schedules (rows-independent fit), then a fit-guarded stream
        # tile, then the per-layer chain — mirroring plan resolution.
        if self.default_path in ("fused", "fused_db") and self._stack_fits:
            return BucketPlan(m, self.default_path, block_m=self.block_m,
                              source="mode")
        bm = self.block_m or 8
        while bm > 8 and not self._schedule_fits("stream", m, bm):
            bm //= 2
        if self._schedule_fits("stream", m, bm):
            return BucketPlan(m, "fused_stream", block_m=bm, source="mode")
        return BucketPlan(m, "per_layer", source="mode")

    def demote_bucket(self, rows: int, *, reason: str = "fault") -> BucketPlan:
        """Graceful-degradation rebind: point one bucket at the per-layer
        chain path.  The serving frontend calls this when a fused
        ``(bucket, schedule)`` entry keeps failing after retries — the
        chain kernels share no schedule (and much less VMEM pressure)
        with the poisoned megakernel entry, so the model keeps serving,
        degraded but correct (chain and megakernel are bit-identical on
        the int8 grid and allclose in fp32 — the parity contract).  The
        jitted entry is dropped so the next launch compiles the fallback;
        the rebind is recorded in ``notes`` and the bucket's ``source``.
        """
        if rows not in self.buckets:
            raise KeyError(f"no bucket of {rows} rows; have "
                           f"{self.bucket_sizes}")
        bp = BucketPlan(rows, "per_layer", source=f"degraded:{reason}")
        self.buckets[rows] = bp
        self._entries.pop(rows, None)
        self.notes.append(
            f"bucket {rows} demoted to per_layer ({reason})")
        return bp

    # ------------------------------------------------------------ execute

    def _execute(self, x: jax.Array, path: str,
                 block_m: Optional[int] = None) -> jax.Array:
        if path == "sharded":
            return self._sharded(x)
        if path == "oracle":
            if self.act_dtype == "int8":
                return kops.fantastic4_mlp_chain_int8(
                    x, self.layers, self.act_scales, use_kernel=False)
            return kops.fantastic4_mlp_chain(x, self.layers,
                                             use_kernel=False)
        if path == "per_layer":
            if self.act_dtype == "int8":
                return kops.fantastic4_mlp_chain_int8(
                    x, self.layers, self.act_scales, use_kernel=True,
                    interpret=self.interpret)
            return kops.fantastic4_mlp_chain(x, self.layers, use_kernel=True,
                                             interpret=self.interpret)
        return kops.fantastic4_mlp_fused(
            x, self.layers, use_kernel=True, interpret=self.interpret,
            block_m=block_m or self.block_m, act_dtype=self.act_dtype,
            act_scales=self.act_scales,
            schedule=SCHEDULE_BY_PATH[path],
            vmem_budget_bytes=self.vmem_budget_bytes)

    def entry(self, bucket: int) -> Callable[[jax.Array], jax.Array]:
        """Shape-stable entry point for one bucket: a callable expecting a
        ``(bucket, d_in)`` input.  Cached per bucket — the underlying
        pallas wrappers are jitted on static shapes, so each bucket
        compiles once and every later call reuses the executable."""
        fn = self._entries.get(bucket)
        if fn is None:
            if bucket not in self.buckets:
                raise KeyError(f"no bucket of {bucket} rows; have "
                               f"{self.bucket_sizes}")
            bp = self.buckets[bucket]

            def fn(xb, _path=bp.path, _bm=bp.block_m, _bucket=bucket):
                assert xb.shape[0] == _bucket, (xb.shape, _bucket)
                return self._execute(xb, _path, block_m=_bm)
            self._entries[bucket] = fn
        return fn

    def run(self, x: jax.Array) -> jax.Array:
        """Serve one batch: pad rows up to the resolved bucket, execute its
        entry, slice the real rows back out.  Batches past the largest
        bucket run at exact size (the megakernel grids over row tiles).
        One call is the profiler span ``serving.plan_run``, with metadata
        ``decode`` (``decode_form`` of the binding that runs)."""
        m = x.shape[0]
        b = self.bucket_for(m)
        bp = self.oversize_binding(m) if b is None else self.buckets[b]
        with TraceAnnotation("serving.plan_run", decode=self.decode_form(bp)):
            x = x.astype(jnp.float32)
            if b is None:
                return self._execute(x, bp.path, block_m=bp.block_m)
            if m < b:
                x = jnp.pad(x, ((0, b - m), (0, 0)))
            return self.entry(b)(x)[:m]

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.run(x)

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> None:
        """Compile (and autotune, on TPU) every bucket entry up front so
        the first real request doesn't pay for it."""
        for b in buckets if buckets is not None else self.bucket_sizes:
            x = jnp.zeros((b, self.d_in), jnp.float32)
            jax.block_until_ready(self.entry(b)(x))

    # ------------------------------------------------------------- report

    def path_for(self, m: int) -> str:
        b = self.bucket_for(m)
        return self.oversize_binding(m).path if b is None \
            else self.buckets[b].path

    def schedule_for(self, m: int) -> str:
        """The kernel schedule that actually executes for ``m`` rows:
        ``"ws" | "batch_tiled" | "db" | "stream"`` on the fused paths,
        else the path name itself (``"per_layer"`` / ``"oracle"``) — the
        label every benchmark row carries."""
        path = self.path_for(m)
        return SCHEDULE_BY_PATH.get(path, path)

    def decode_form(self, bp: BucketPlan) -> str:
        """How one call of a binding decodes the 4-bit codes: ``"once"``
        per layer, or ``"per_tile"``, again in each row tile.  The
        batch-tiled kernel decodes once when it runs several tiles and the
        decoded stack fits VMEM (``fused_mlp_decode_once``), else per tile
        (a single tile included); ws, stream and the jnp oracle decode
        once; the per-layer kernel, which the sharded program runs too,
        per tile."""
        form = self._decode_memo.get(bp)
        if form is None:
            if bp.path in ("fused", "fused_db"):
                once = fused_mlp_decode_once(
                    self.shapes, bp.rows, bp.block_m or self.block_m,
                    self.act_dtype, double_buffer=bp.path == "fused_db")
            else:
                once = bp.path not in ("per_layer", "sharded")
            form = self._decode_memo[bp] = "once" if once else "per_tile"
        return form

    def describe(self) -> dict:
        return {
            "requested_mode": self.requested_mode,
            "resolved_mode": self.resolved_mode,
            "act_dtype": self.act_dtype,
            "block_m": self.block_m,
            "block_source": self.block_source,
            "bucket_sizes": list(self.bucket_sizes),
            "bucket_paths": {b: p.path for b, p in self.buckets.items()},
            "bucket_schedules": {
                b: SCHEDULE_BY_PATH.get(p.path, p.path)
                for b, p in self.buckets.items()},
            "bucket_block_m": {b: p.block_m
                               for b, p in self.buckets.items()},
            "bucket_sources": {b: p.source
                               for b, p in self.buckets.items()},
            "bucket_decode": {b: self.decode_form(p)
                              for b, p in self.buckets.items()},
            # the batches past the largest bucket served so far
            "oversize_bindings": {
                m: {"path": p.path, "block_m": p.block_m,
                    "decode": self.decode_form(p)}
                for m, p in self._oversize_memo.items()},
            "ws_crossover_rows": self.ws_crossover_rows,
            "ws_prior_rows": self.ws_prior_rows,
            "ws_prior_source": self.ws_prior_source,
            "default_path": self.default_path,
            "interpret": self.interpret,
            "sharding": (None if self._sharded is None
                         else self._sharded.describe()),
            # per-layer content digests when the pack was stamped at
            # freeze/decode time (None entries on legacy packs) — lets
            # operators fingerprint exactly which weights are serving
            "layer_crcs": [layer.get("crc")
                           for layer in self.layers],
            "notes": list(self.notes),
        }

    def mode_label(self, m: Optional[int] = None) -> str:
        """Human-readable label of what will actually execute (for ``m``
        rows when given, otherwise the plan as a whole)."""
        names = {"fused": "fused megakernel",
                 "fused_db": "fused megakernel (double-buffered)",
                 "fused_ws": "fused megakernel (weight-stationary)",
                 "fused_stream": "fused megakernel (streaming)",
                 "sharded": "column-sharded multi-device stack",
                 "per_layer": "per-layer kernel",
                 "oracle": "jnp oracle"}
        if m is not None:
            label = names[self.path_for(m)]
        else:
            paths = {p.path for p in self.buckets.values()}
            label = " / ".join(names[p] for p in
                               ("fused_ws", "fused", "fused_db",
                                "fused_stream", "sharded", "per_layer",
                                "oracle")
                               if p in paths)
        if self.act_dtype == "int8":
            label += " [int8 activations]"
        return label


def build_plan(pack: dict, **kwargs) -> ExecutionPlan:
    """Resolve an :class:`ExecutionPlan` for a frozen pack (see the class
    for the knobs).  One call per pack per configuration — use
    :func:`get_plan` from per-request code paths."""
    return ExecutionPlan(pack, **kwargs)


# plan memoization per (pack identity, configuration): request-path callers
# (models.mlp compat wrappers, the launcher) must not re-resolve fits /
# autotune / calibration per call.  Identity keying is safe because frozen
# packs are never mutated in place (see repro.memo).
#
# Lifetime contract (the serving stack is keyed off the pack cache, this
# memo is the *compat-wrapper* path): a plan the ``serving.pack_cache``
# resolves is ADOPTED here pinned (``adopt_plan``), so a compat caller
# hitting ``get_plan`` on the same pack+configuration gets the cache's
# plan instead of silently re-resolving a duplicate (double device
# memory, a cold re-jit on the request path — the pre-fix bug when the
# memo's 32-entry insertion-order eviction dropped an entry a frontend
# still served from).  Eviction/unregistration calls ``forget_plan``,
# which releases the memo entries AND the kernel-level operand caches —
# the memo can never outlive a cache-managed plan.
_PLAN_MEMO = IdentityMemo()


def get_plan(pack: dict, *, calib: Optional[dict] = None,
             **kwargs) -> ExecutionPlan:
    extra = tuple(sorted(kwargs.items()))
    hit = _PLAN_MEMO.get((pack, calib), extra)
    if hit is not MISS:
        return hit
    plan = ExecutionPlan(pack, calib=calib, **kwargs)
    _PLAN_MEMO.put((pack, calib), extra, plan)
    return plan


def adopt_plan(pack: dict, plan: ExecutionPlan, *,
               calib: Optional[dict] = None, **kwargs) -> None:
    """Register an externally-managed (pack-cache) plan under the same
    key ``get_plan(pack, calib=calib, **kwargs)`` would compute, pinned:
    the memo's insertion-order eviction never drops it, so the compat
    path can never resolve a duplicate beside it.  Release is explicit,
    via :func:`forget_plan`."""
    _PLAN_MEMO.put((pack, calib), tuple(sorted(kwargs.items())), plan,
                   pin=True)


def forget_plan(pack: dict) -> None:
    """Release every plan-side cache entry keyed on ``pack``: the plan
    memo entries (pinned or not) and the kernel-level folded-int8 /
    weight-stationary operand memos keyed on the pack's layer list.
    Called by the pack cache on eviction and by
    ``ModelRegistry.unregister`` — without it a retired model's decoded
    operands and jitted entries survive for the process lifetime even
    though no frontend can reach them."""
    _PLAN_MEMO.drop(pack)
    layers = pack.get("layers") if isinstance(pack, dict) else None
    if layers is not None:
        kops.forget_pack_operands(layers)
