"""Shape-aware block-size autotuner for the FantastIC4 Pallas kernels.

The seed kernels ran every shape with hard-coded ``block_m=128 / block_n=256
/ block_k=512``; paper-shaped layers (512×512 down to 128×12) and serving
batches (1…256) leave most of those tiles as padding.  This module picks
per-shape blocks instead, in three tiers:

1. **memory cache** — a dict keyed by
   ``(backend, M, K, N, dtype, fused, act_dtype)``; ``act_dtype`` is the
   serving path's inter-layer activation dtype (the fused kernel's int8
   mode has a different body — extra quantize/cast per layer — so its best
   block must not shadow the fp32 sweep for the same shape).  Cache files
   written before this field existed are migrated on load: their keys are
   re-interpreted as ``act_dtype=float32`` entries.
2. **persistent JSON cache** — survives processes, so the timed sweep runs
   once per shape per host.  Location: ``$FANTASTIC4_AUTOTUNE_CACHE`` or
   ``~/.cache/fantastic4/autotune.json``.
3. **resolution** — on a real accelerator a *timed candidate sweep* (the
   caller supplies ``measure``, a ``BlockConfig -> seconds`` closure running
   the actual kernel; AttentionEngine-style empirical tuning); in
   interpret/CPU mode a *pure heuristic* (timing the interpreter is
   meaningless), which clamps blocks to the padded problem dims so small
   layers stop paying for 128×256×512 tiles.

``ops.fantastic4_matmul`` / ``ops.fantastic4_mlp_fused`` consult this module
whenever a block size is left as ``None`` — the default for every entry
point (serving launcher, benchmarks, models), so all of them exercise the
same tuned configuration.

**Autotuner v2 — schedule-aware bucket tuning.**  The serving engine runs
four fused kernel schedules (batch-tiled / double-buffered / weight-
stationary / decode-amortized streaming) and the right one depends on the
batch bucket, not just the shape: the tuning unit is ``(bucket_rows,
schedule)``.  :func:`get_schedule_config` resolves one bucket's binding —
a timed sweep over every eligible ``(schedule, block_m)`` candidate on a
real backend, a dataflow prior plus migration from the old single-entry
fused keys otherwise — and persists it in the same JSON cache under a
``…|bucket`` key whose value carries a ``schedule`` field.  Old cache
files (block-only values, single fused entry tuned at the largest bucket)
load unchanged and seed the per-bucket entries instead of being
discarded.  The measured ws↔batch-tiled crossover row count is stored
alongside (:func:`record_ws_crossover` / :func:`get_ws_crossover`) so a
committed TPU cache replaces the ``WS_BUCKET_ROWS`` constant with a
measurement.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import threading
import warnings
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax

ENV_CACHE = "FANTASTIC4_AUTOTUNE_CACHE"

# sublane/lane granularity of a f32 TPU tile; block dims are clamped to
# multiples of these so padding stays inside one tile.
SUBLANE = 8
LANE = 128

# the fused megakernel schedules a bucket can bind to (serving.plans maps
# these onto its bucket paths); "ws_crossover" additionally marks the
# stored ws↔batch-tiled crossover entry, which is metadata, not a schedule.
SCHEDULES = ("ws", "batch_tiled", "db", "stream")


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    block_m: int
    block_n: int
    block_k: int
    source: str = "heuristic"  # "heuristic" | "sweep" | "cache" | "migrated"
    schedule: Optional[str] = None     # set on (bucket, schedule) entries
    # the eligible set a (bucket, schedule) sweep actually measured over:
    # a cached winner only answers callers whose eligible set it covered
    # (a ws-opt-out plan's sweep must not shadow a default plan's)
    swept: Optional[Tuple[str, ...]] = None

    def as_tuple(self) -> tuple:
        return (self.block_m, self.block_n, self.block_k)

    def same_blocks(self, other: "BlockConfig") -> bool:
        return self.as_tuple() == other.as_tuple()


_lock = threading.Lock()
_memory: Dict[str, BlockConfig] = {}
_disk_loaded_for: Optional[str] = None
# per thread, the lists open collectors append failed sweep candidates to
# (a plan resolves its sweeps in the thread that builds it)
_collectors = threading.local()


@contextlib.contextmanager
def collect_failures(sink: List[str]):
    """Append to ``sink`` every sweep candidate that fails in this thread
    while the block is open, as ``"sweep candidate failed: <cache key>
    <candidate>: <error>"``.  Collectors nest, each open one receiving the
    failure; with none open, a failure is a ``RuntimeWarning``."""
    if not hasattr(_collectors, "sinks"):
        _collectors.sinks = []
    _collectors.sinks.append(sink)
    try:
        yield sink
    finally:
        _collectors.sinks.pop()


def _sweep(key: str, cands: Sequence, measure: Callable[..., float]
           ) -> List[float]:
    """Seconds per candidate (``measure(*cand)`` for tuples).  ``inf``
    from ``measure`` means "not eligible"; a candidate that raises goes to
    the open :func:`collect_failures` sinks and is timed ``inf``.  When no
    candidate ran and at least one raised, the sweep raises: a binding
    chosen without any measurement would hide the failure."""
    times, errors = [], []
    for cand in cands:
        try:
            times.append(measure(*cand) if isinstance(cand, tuple)
                         else measure(cand))
        except Exception as e:              # noqa: BLE001 — recorded
            errors.append(f"sweep candidate failed: {key} {cand}: "
                          f"{type(e).__name__}: {e}".splitlines()[0])
            times.append(float("inf"))
    if errors:
        sinks = getattr(_collectors, "sinks", [])
        for sink in sinks:
            sink.extend(errors)
        if not sinks:
            warnings.warn("\n".join(errors), RuntimeWarning, stacklevel=2)
        if all(t == float("inf") for t in times):
            raise RuntimeError("every sweep candidate failed:\n"
                               + "\n".join(errors))
    return times


def _round_up(v: int, mult: int) -> int:
    return -(-max(v, 1) // mult) * mult


def cache_path() -> str:
    return os.environ.get(ENV_CACHE) or os.path.join(
        os.path.expanduser("~"), ".cache", "fantastic4", "autotune.json")


def cache_key(m: int, k: int, n: int, *, dtype: str, fused: bool,
              backend: str, act_dtype: str = "float32",
              extra: str = "") -> str:
    """``extra`` disambiguates problems that share (M, K, N) — e.g. a fused
    stack's intermediate widths, which (M, K₀, N_last) alone cannot see."""
    tail = f"|{extra}" if extra else ""
    return (f"{backend}|m{m}|k{k}|n{n}|{dtype}|fused{int(fused)}"
            f"|act{act_dtype}{tail}")


def _migrate_key(key: str) -> str:
    """Rewrite a pre-act_dtype cache key to the current format.

    Old keys read ``backend|m..|k..|n..|dtype|fusedX[|extra]``; the act
    segment slots in after ``fusedX`` as ``actfloat32`` (the only act dtype
    that existed then).  Current-format keys pass through unchanged."""
    segs = key.split("|")
    for i, seg in enumerate(segs):
        if seg.startswith("fused") and seg[5:].isdigit():
            if i + 1 < len(segs) and segs[i + 1].startswith("act"):
                return key
            return "|".join(segs[:i + 1] + ["actfloat32"] + segs[i + 1:])
    return key


def clear_memory_cache() -> None:
    """Drop the in-process cache (tests; the JSON file is untouched)."""
    global _disk_loaded_for
    with _lock:
        _memory.clear()
        _disk_loaded_for = None


def _load_disk_locked() -> None:
    global _disk_loaded_for
    path = cache_path()
    if _disk_loaded_for == path:
        return
    _disk_loaded_for = path
    if not os.path.exists(path):
        return
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return
    for key, v in raw.items():
        try:
            sched = v.get("schedule")
            swept = v.get("swept")
            cfg = BlockConfig(int(v["block_m"]), int(v["block_n"]),
                              int(v["block_k"]),
                              source=v.get("source", "cache"),
                              schedule=str(sched) if sched else None,
                              swept=tuple(str(s) for s in swept)
                              if swept else None)
        except (KeyError, TypeError, ValueError):
            continue                     # stale/corrupt entry: ignore
        key = _migrate_key(key)          # pre-act_dtype files -> actfloat32
        if key not in _memory:
            _memory[key] = cfg


def _save_disk_locked() -> None:
    path = cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    payload = {}
    for key, c in sorted(_memory.items()):
        entry = {"block_m": c.block_m, "block_n": c.block_n,
                 "block_k": c.block_k, "source": c.source}
        if c.schedule is not None:       # block-only entries keep the old
            entry["schedule"] = c.schedule   # format byte for byte
        if c.swept is not None:
            entry["swept"] = list(c.swept)
        payload[key] = entry
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2)
    os.replace(tmp, path)


def heuristic_blocks(m: int, k: int, n: int, *, fused: bool = False,
                     backend: Optional[str] = None) -> BlockConfig:
    """Shape-clamped blocks, no timing.

    The guiding costs: (a) never tile past the (tile-rounded) problem dims —
    a 128-wide layer must not pay for a 256-wide block of padding; (b) on a
    real TPU keep x-tile + packed-tile + decoded-W-tile + acc inside a
    conservative VMEM slice; (c) in interpret mode grid steps are the cost,
    so take whole (rounded) dims up to a cap.  Fused kernels tile only over
    M (weights/activations are VMEM-resident), so block_n/block_k are the
    rounded full dims.
    """
    backend = backend or jax.default_backend()
    mp = _round_up(m, SUBLANE)
    np_ = _round_up(n, LANE)
    kp = _round_up(k, LANE)
    if fused or backend != "tpu":
        # one grid axis (fused) / interpreter (CPU): minimise grid steps.
        return BlockConfig(min(mp, 256), min(np_, 1024), min(kp, 2048))
    # TPU per-layer kernel: MXU-friendly tiles clamped to the problem.
    bm = min(mp, 128)
    bn = min(np_, 256)
    bk = min(kp, 512)
    # keep x(bm,bk)f32 + packed(bk/2,bn)u8 + W(bk,bn)f32 + acc(bm,bn)f32
    # comfortably under a ~4 MiB working-set slice of VMEM.
    def _bytes(bm, bn, bk):
        return 4 * bm * bk + bk * bn // 2 + 4 * bk * bn + 4 * bm * bn
    while _bytes(bm, bn, bk) > 4 << 20 and bk > LANE:
        bk //= 2
    while _bytes(bm, bn, bk) > 4 << 20 and bn > LANE:
        bn //= 2
    return BlockConfig(bm, bn, bk)


def heuristic_elementwise_blocks(r: int, c: int, *,
                                 backend: Optional[str] = None
                                 ) -> BlockConfig:
    """Shape-clamped tiles for 2-D elementwise kernels (``ecl_quant``).

    ``block_k`` is meaningless for an elementwise grid and is pinned to 0
    (the sentinel the cache key carries).  Costs mirror
    :func:`heuristic_blocks`: clamp to the (tile-rounded) problem, minimise
    grid steps in interpret mode, and on TPU keep the w/codes/w_hat tiles
    (4 + 1 + 4 bytes per element) inside a conservative VMEM slice.
    """
    backend = backend or jax.default_backend()
    rp = _round_up(r, SUBLANE)
    cp = _round_up(c, LANE)
    if backend != "tpu":
        return BlockConfig(min(rp, 512), min(cp, 1024), 0)
    br, bc = min(rp, 256), min(cp, 512)
    while 9 * br * bc > (4 << 20) and bc > LANE:
        bc //= 2
    while 9 * br * bc > (4 << 20) and br > SUBLANE:
        br //= 2
    return BlockConfig(br, bc, 0)


def candidate_elementwise_blocks(r: int, c: int) -> Sequence[BlockConfig]:
    """Candidate (block_r, block_c) grid for the elementwise timed sweep."""
    rp, cp = _round_up(r, SUBLANE), _round_up(c, LANE)
    brs = sorted({min(rp, v) for v in (64, 128, 256, 512)})
    bcs = sorted({min(cp, v) for v in (128, 256, 512, 1024)})
    return [BlockConfig(br, bc, 0, source="sweep")
            for br in brs for bc in bcs]


def _resolve_and_cache(key: str, *,
                       measure: Optional[Callable[[BlockConfig], float]],
                       candidates: Callable[[], Iterable[BlockConfig]],
                       heuristic: Callable[[], BlockConfig],
                       persist: bool) -> BlockConfig:
    """Shared cache → timed-sweep → heuristic tiering (one implementation
    for the matmul and elementwise entry points).  ``candidates`` and
    ``heuristic`` are thunks so neither is built on a cache hit."""
    with _lock:
        _load_disk_locked()
        hit = _memory.get(key)
    if hit is not None:
        return hit
    if measure is None:
        cfg = heuristic()
    else:
        cands = list(candidates())
        best_t, best_i = min(
            (t, i) for i, t in enumerate(_sweep(key, cands, measure)))
        if best_t == float("inf"):
            # nothing eligible: the heuristic answers, uncached, so it
            # never masks a later measurement under this backend's key
            return heuristic()
        cfg = dataclasses.replace(cands[best_i], source="sweep")
    with _lock:
        _memory[key] = cfg
        if persist:
            try:
                _save_disk_locked()
            except OSError:
                pass                      # read-only FS: memory cache only
    return cfg


def get_elementwise_config(r: int, c: int, *,
                           dtype: str = "float32",
                           backend: Optional[str] = None,
                           measure: Optional[
                               Callable[[BlockConfig], float]] = None,
                           op: str = "eclquant",
                           persist: bool = True) -> BlockConfig:
    """Resolve (block_r, block_c) for a 2-D elementwise kernel.

    Same cache → sweep → heuristic tiering as :func:`get_block_config`;
    entries live in the same store under ``k=0`` plus an ``op`` extra, so
    they can never collide with a matmul shape's blocks.
    """
    backend = backend or jax.default_backend()
    return _resolve_and_cache(
        cache_key(r, 0, c, dtype=dtype, fused=False, backend=backend,
                  extra=op),
        measure=measure,
        candidates=lambda: candidate_elementwise_blocks(r, c),
        heuristic=lambda: heuristic_elementwise_blocks(r, c,
                                                       backend=backend),
        persist=persist)


def candidate_blocks(m: int, k: int, n: int, *, fused: bool = False
                     ) -> Sequence[BlockConfig]:
    """Candidate grid for the timed sweep (deduped, shape-clamped)."""
    mp, np_, kp = _round_up(m, SUBLANE), _round_up(n, LANE), _round_up(k, LANE)
    bms = sorted({min(mp, v) for v in (32, 64, 128, 256)})
    if fused:
        return [BlockConfig(bm, min(np_, 1024), min(kp, 2048), source="sweep")
                for bm in bms]
    bns = sorted({min(np_, v) for v in (128, 256, 512)})
    bks = sorted({min(kp, v) for v in (128, 256, 512, 1024)})
    return [BlockConfig(bm, bn, bk, source="sweep")
            for bm in bms for bn in bns for bk in bks]


def get_block_config(m: int, k: int, n: int, *,
                     dtype: str = "float32", fused: bool = False,
                     backend: Optional[str] = None,
                     measure: Optional[Callable[[BlockConfig], float]] = None,
                     candidates: Optional[Iterable[BlockConfig]] = None,
                     act_dtype: str = "float32",
                     extra: str = "",
                     persist: bool = True) -> BlockConfig:
    """Resolve blocks for one problem shape (cache → sweep → heuristic).

    ``measure`` runs one candidate and returns seconds (``inf`` = not
    eligible; a candidate that raises is recorded, see :func:`_sweep`);
    when omitted — the interpret/CPU path — the
    heuristic answers directly.  Results land in the memory cache and, when
    ``persist``, the JSON cache, so a warm call never re-measures.

    Callers running in interpret mode must pass ``backend="interpret"``:
    keying those heuristic answers under the real backend would permanently
    mask the timed sweep for the same shape on actual hardware.
    """
    backend = backend or jax.default_backend()
    return _resolve_and_cache(
        cache_key(m, k, n, dtype=dtype, fused=fused, backend=backend,
                  act_dtype=act_dtype, extra=extra),
        measure=measure,
        candidates=lambda: (candidates if candidates is not None
                            else candidate_blocks(m, k, n, fused=fused)),
        heuristic=lambda: heuristic_blocks(m, k, n, fused=fused,
                                           backend=backend),
        persist=persist)


# --------------------------------------- v2: (bucket, schedule) tuning unit

def bucket_cache_key(rows: int, k: int, n: int, *, dtype: str = "float32",
                     backend: Optional[str] = None,
                     act_dtype: str = "float32", stack: str = "") -> str:
    """Key of one batch bucket's (schedule, block_m) binding."""
    backend = backend or jax.default_backend()
    return cache_key(rows, k, n, dtype=dtype, fused=True, backend=backend,
                     act_dtype=act_dtype,
                     extra=(f"{stack}|" if stack else "") + "bucket")


def ws_crossover_key(k: int, n: int, *, dtype: str = "float32",
                     backend: Optional[str] = None,
                     act_dtype: str = "float32", stack: str = "") -> str:
    backend = backend or jax.default_backend()
    return cache_key(0, k, n, dtype=dtype, fused=True, backend=backend,
                     act_dtype=act_dtype,
                     extra=(f"{stack}|" if stack else "") + "wscross")


def candidate_schedule_blocks(rows: int, schedules: Sequence[str]
                              ) -> Sequence[Tuple[str, int]]:
    """Candidate (schedule, block_m) grid for one bucket's timed sweep.

    ``ws`` holds the whole (padded) bucket in its scratch — block_m is not
    a free variable there; the tiled schedules sweep the shape-clamped
    block_m ladder (``db`` needs two whole sublane groups per tile, so its
    candidates keep to multiples of 16).
    """
    mp = _round_up(rows, SUBLANE)
    out = []
    for sched in schedules:
        if sched == "ws":
            out.append((sched, mp))
            continue
        bms = sorted({min(mp, v) for v in (32, 64, 128, 256)})
        if sched == "db":
            bms = [b for b in bms if b % 16 == 0]
        out.extend((sched, bm) for bm in bms)
    return out


def get_schedule_config(rows: int, k: int, n: int, *,
                        schedules: Sequence[str],
                        prior: str,
                        dtype: str = "float32",
                        backend: Optional[str] = None,
                        act_dtype: str = "float32",
                        stack: str = "",
                        measure: Optional[
                            Callable[[str, int], float]] = None,
                        legacy_m: Optional[int] = None,
                        block_m_hint: Optional[int] = None,
                        persist: bool = True) -> BlockConfig:
    """Resolve one batch bucket's (schedule, block_m) binding.

    ``schedules`` is the bucket's *eligible* set (VMEM-fit and opt-outs
    already applied by the caller, in plans); ``prior`` the dataflow-
    motivated pre-measurement answer.  ``measure(schedule, block_m) ->
    seconds`` runs the actual kernel on a real backend (``inf`` = not
    eligible; a candidate that raises is recorded, see :func:`_sweep`);
    without it — the interpret/CPU tier, where timing
    the interpreter is meaningless — the prior answers, with ``block_m``
    migrated from the old single-entry fused key (``legacy_m`` = the rows
    it was tuned at) or from ``block_m_hint`` rather than re-derived.

    Cache-validity is *eligibility-aware*: an entry records the set it was
    swept over (``swept``) and only answers callers whose eligible set it
    covered.  When coverage is incomplete (or the cached winner is one the
    caller forbids — e.g. a measured ``ws`` binding under
    ``ws_bucket_rows=0`` opt-out) and a ``measure`` is available, the
    sweep runs over the *union* of the caller's set and the entry's
    covered set: the stored entry becomes the union's winner (valid for
    every caller the union covers, so two plans with different eligible
    sets converge instead of alternately re-sweeping and shadowing each
    other), while the caller receives the best candidate *it* is allowed
    to bind.  Without a measure, a forbidden winner is bypassed but not
    overwritten — the prior answers uncached and the measurement survives.
    """
    if not schedules:
        raise ValueError("schedules must name at least one eligible "
                         "schedule")
    unknown = [s for s in schedules if s not in SCHEDULES]
    if unknown:
        raise ValueError(f"unknown schedules {unknown}; valid: {SCHEDULES}")
    if prior not in schedules:
        prior = schedules[0]
    backend = backend or jax.default_backend()
    key = bucket_cache_key(rows, k, n, dtype=dtype, backend=backend,
                           act_dtype=act_dtype, stack=stack)
    with _lock:
        _load_disk_locked()
        hit = _memory.get(key)
    covered: set = set()
    if hit is not None:
        covered = set(hit.swept) if hit.swept else \
            ({hit.schedule} if hit.schedule else set())
        # a hit answers only when its sweep covered every schedule this
        # caller may bind (else a restricted plan's winner would shadow
        # the broader sweep); without a measure it is still the best
        # measurement this backend has, so take it.
        if hit.schedule in schedules and \
                (set(schedules) <= covered or measure is None):
            return hit
    mp = _round_up(rows, SUBLANE)
    cfg = None
    store = None
    if measure is not None:
        # sweep the union of the caller's set and whatever the existing
        # entry had covered: the stored result then answers both this
        # caller and the ones the old entry served, so plans with
        # different eligible sets converge on one complete entry instead
        # of alternately re-sweeping and shadowing each other.
        sweep_set = tuple(schedules) + tuple(
            s for s in SCHEDULES if s in covered and s not in schedules)
        cands = list(candidate_schedule_blocks(rows, sweep_set))
        finite = [(t, i) for i, t in enumerate(_sweep(key, cands, measure))
                  if t != float("inf")]
        caller_finite = [(t, i) for t, i in finite
                         if cands[i][0] in schedules]
        if caller_finite:
            t, i = min(caller_finite)
            s, bm = cands[i]
            cfg = BlockConfig(bm, 0, 0, source="sweep", schedule=s,
                              swept=sweep_set)
            tu, iu = min(finite)
            if iu == i:
                store = cfg
            else:                        # union winner differs: store it,
                su, bmu = cands[iu]      # hand the caller its own best
                store = BlockConfig(bmu, 0, 0, source="sweep",
                                    schedule=su, swept=sweep_set)
    if cfg is None:
        bm, source = None, "heuristic"
        if legacy_m is not None:
            # old single-entry fused key: one block_m tuned at the largest
            # bucket — reuse it (clamped to this bucket) instead of
            # discarding the measurement.
            with _lock:
                legacy = _memory.get(cache_key(
                    legacy_m, k, n, dtype=dtype, fused=True,
                    backend=backend, act_dtype=act_dtype, extra=stack))
            if legacy is not None:
                bm, source = min(legacy.block_m, mp), "migrated"
        if bm is None and block_m_hint is not None:
            bm = min(block_m_hint, mp)
        if bm is None:
            bm = heuristic_blocks(rows, k, n, fused=True,
                                  backend=backend).block_m
        cfg = BlockConfig(bm, 0, 0, source=source, schedule=prior)
    if store is None:
        # prior/migrated answers depend on the *caller's* eligibility and
        # requests (ws opt-out, double_buffer) — caching them would let one
        # plan's configuration shadow another's, and would mask the real
        # backend's future sweep.  Only measurements enter the cache.
        return cfg
    with _lock:
        _memory[key] = store
        if persist:
            try:
                _save_disk_locked()
            except OSError:
                pass
    return cfg


def record_ws_crossover(rows: int, k: int, n: int, *,
                        dtype: str = "float32",
                        backend: Optional[str] = None,
                        act_dtype: str = "float32", stack: str = "",
                        persist: bool = True) -> None:
    """Persist the measured ws↔batch-tiled crossover: the largest bucket
    row count at which the weight-stationary schedule won the sweep (0 =
    ws never won).  Replaces the ``WS_BUCKET_ROWS`` constant as the plan's
    gate once a real backend has measured."""
    backend = backend or jax.default_backend()
    key = ws_crossover_key(k, n, dtype=dtype, backend=backend,
                           act_dtype=act_dtype, stack=stack)
    cfg = BlockConfig(int(rows), 0, 0, source="sweep",
                      schedule="ws_crossover")
    with _lock:
        _load_disk_locked()      # merge with existing entries, never clobber
        _memory[key] = cfg
        if persist:
            try:
                _save_disk_locked()
            except OSError:
                pass


def get_ws_crossover(k: int, n: int, *, dtype: str = "float32",
                     backend: Optional[str] = None,
                     act_dtype: str = "float32",
                     stack: str = "") -> Optional[int]:
    """Measured ws↔batch-tiled crossover row count, or None if this
    backend has never swept the stack."""
    backend = backend or jax.default_backend()
    key = ws_crossover_key(k, n, dtype=dtype, backend=backend,
                           act_dtype=act_dtype, stack=stack)
    with _lock:
        _load_disk_locked()
        hit = _memory.get(key)
    if hit is None or hit.schedule != "ws_crossover":
        return None
    return hit.block_m
