"""Open-loop single-row requests through ``ServingFrontend.submit``.

The cell's data file gives the offered rate, the arrival process, the SLO
tier or the batching delay, and the number of execution streams (one per
chip).  Every seed offers the same work: ``round(rate * seconds)``
requests whose gaps are the same set of quantiles of the arrival process,
in an order drawn from the seed, over rows drawn from a pool of
``POOL_ROWS`` made from the seed.

Each request is timed from the instant it was due on the schedule to the
moment its result reached the client (the future's done callback, on the
benchmark's clock), so a stall counts against every request behind it.
A request that is shed, rejected or failed counts in ``failed`` and misses
every latency.  How late the generator sent each request is recorded,
and so are the interpreter's garbage collections inside the window.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import math
import time
from typing import Optional

import numpy as np

from .stats import percentile
from .trace import WINDOW_SPAN

MODEL = "model"
WAIT_SPAN = "chipbench.generator.wait"
DRAIN_S = 60.0
POOL_ROWS = 4096        # distinct rows the requests draw from
CHECK_REQUESTS = 2048   # requests whose answers are checked
WARM_SECONDS = 1.0      # warm-up traffic at the cell's own rate
STALL_MS = 20.0         # a send this late marks a stall of the process


def offsets(n: int, rate: float, seconds: float, arrivals: dict,
            rng: np.random.Generator) -> np.ndarray:
    """Send offsets (s) of ``n`` requests spread over ``seconds``.

    ``poisson``: exponential gaps at ``rate``.  ``onoff``: phases of
    ``phase_requests`` requests that alternate between ``on_factor`` and
    ``off_factor`` times the rate.  The gaps are the midpoint quantiles of
    the exponential distribution, shuffled by ``rng``, scaled so that the
    schedule spans the window."""
    q = (np.arange(n) + 0.5) / n
    unit = -np.log1p(-q)                       # exponential, mean 1
    process = arrivals.get("process", "poisson")
    if process == "poisson":
        gaps = unit / rate
        rng.shuffle(gaps)
    elif process == "onoff":
        per = int(arrivals["phase_requests"])
        phase_on = (np.arange(n) // per) % 2 == 0
        gaps = np.empty(n)
        for on, factor in ((True, arrivals["on_factor"]),
                           (False, arrivals["off_factor"])):
            sel = phase_on == on
            g = np.quantile(unit, (np.arange(sel.sum()) + 0.5)
                            / max(sel.sum(), 1)) / (rate * factor)
            rng.shuffle(g)
            gaps[sel] = g
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


class OpenLoop:
    """The generator of ``online`` traffic."""

    def __init__(self, run):
        self.run = run
        p = run.params
        self.rate = float(p["rate"])
        self.tier = p.get("tier")
        self.max_delay = p.get("max_delay_s")
        self.streams = int(p.get("streams", 1))
        self.arrivals = p.get("arrivals", {"process": "poisson"})
        self.fe = None

    # ------------------------------------------------------------ set-up

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro import serving
        run = self.run
        self.pool = run.rng(1).standard_normal(
            (POOL_ROWS, run.model.d_in), dtype=np.float32)
        plan = run.plan
        # every bucket on every stream's chip: a launch there compiles its
        # own executable
        devices = jax.devices()[:self.streams] if self.streams > 1 \
            else [None]
        for dev in devices:
            with jax.default_device(dev) if dev is not None \
                    else contextlib.nullcontext():
                for b in plan.bucket_sizes:
                    x = jnp.asarray(np.zeros((b, plan.d_in), np.float32))
                    jax.block_until_ready(plan.entry(b)(x))
        self.fe = serving.ServingFrontend(streams=self.streams).start()
        self.fe.register(MODEL, plan, tier=self.tier,
                         max_delay=self.max_delay)
        self.batcher = self.fe.registry.batcher(MODEL)
        # the serving path's host code, the batcher's cost model and every
        # bucket the traffic reaches, warmed at the cell's own rate
        self.window(WARM_SECONDS, rng_stream=5)

    # ------------------------------------------------------------ window

    def _counters(self) -> dict:
        st = self.batcher.stats
        return {"launches": st["flushes"], "rows": st["flushed_rows"],
                "padded": st["padded_rows"]}

    def window(self, seconds: float, rate: Optional[float] = None,
               rng_stream: int = 4) -> dict:
        import jax
        rate = self.rate if rate is None else rate
        rng = self.run.rng(rng_stream)
        n = max(1, int(round(rate * seconds)))
        due_off = offsets(n, rate, seconds, self.arrivals, rng)
        rows_idx = rng.integers(0, POOL_ROWS, n)
        # the requests whose answers are checked, drawn before the window;
        # no other request's future or answer is kept past its completion
        want = np.zeros(n, bool)
        want[rng.choice(n, size=min(CHECK_REQUESTS, n),
                        replace=False)] = True
        kept = {}
        pool, fe = self.pool, self.fe
        clock = time.perf_counter
        done = np.full(n, np.nan)
        bad = np.zeros(n, bool)
        sent = np.empty(n)

        def mark(i, fut):
            done[i] = clock()
            if fut.cancelled() or fut.exception() is not None:
                bad[i] = True
            elif want[i]:
                kept[i] = fut.result().y

        def wait(dt):
            # in a traced run the idle gaps this leaves on the device are
            # named after it: no request was due
            if traced:
                with jax.profiler.TraceAnnotation(WAIT_SPAN):
                    time.sleep(dt)
            else:
                time.sleep(dt)

        collections = []

        def on_gc(phase, info):
            collections.append((phase, info["generation"], clock()))

        traced = bool(self.run.args.trace)
        start = self._counters()
        gc.callbacks.append(on_gc)
        t0 = clock() + 1e-3
        due = t0 + due_off
        t_end = t0 + seconds
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            for i in range(n):
                d = due[i]
                now = clock()
                if now < d:
                    wait(d - now)
                    now = clock()
                j = rows_idx[i]
                sent[i] = now
                fe.submit(MODEL, pool[j:j + 1]).add_done_callback(
                    functools.partial(mark, i))
            now = clock()
            if now < t_end:
                wait(t_end - now)
            end = self._counters()
            queued = int(np.isnan(done).sum())
        t_close = clock()
        gc.callbacks.remove(on_gc)
        while np.isnan(done).any() and clock() < t_close + DRAIN_S:
            time.sleep(0.005)
        never = np.isnan(done)
        bad |= never
        lat = (done - due) * 1e3
        lat[bad] = math.inf
        rows_done = int(np.sum((done <= t_end) & ~bad))
        return {
            "attempted": n, "failed": int(bad.sum()),
            "failed_check": int(never.sum()),
            "queued_at_close": queued, "window_s": seconds,
            "drain_s": clock() - t_close,
            "latencies_ms": lat, "gen_late_ms": (sent - due) * 1e3,
            "rows_done": rows_done, "rows_per_s": rows_done / seconds,
            "offered_per_s": n / seconds,
            "launches": end["launches"] - start["launches"],
            "launch_rows": end["rows"] - start["rows"],
            "padded_rows": end["padded"] - start["padded"],
            "kept": kept, "rows_idx": rows_idx,
            "t0_epoch": time.time() - (clock() - t0),
            "stalls_at_s": _stalls(due_off, (sent - due) * 1e3, STALL_MS),
            "gc": _gc_summary(collections),
        }

    def summary(self, w: dict) -> dict:
        lat = w["latencies_ms"]
        return {"offered_per_s": w["offered_per_s"],
                "attempted": w["attempted"], "failed": w["failed"],
                "queued_at_close": w["queued_at_close"],
                "completed_in_window": w["rows_done"],
                "completed_over_offered": w["rows_done"] / w["attempted"],
                "drain_s": w["drain_s"],
                "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
                "p99_ms": percentile(lat, 99), "max_ms": float(np.max(lat)),
                "gen_late_p99_ms": percentile(w["gen_late_ms"], 99),
                "launches": w["launches"], "launch_rows": w["launch_rows"],
                "padded_rows": w["padded_rows"], "t0_epoch": w["t0_epoch"],
                "stalls_at_s": w["stalls_at_s"], "gc": w["gc"]}

    # -------------------------------------------------------------- check

    def close(self) -> None:
        if self.fe is not None:
            self.fe.close()
            self.fe = None

    def sample(self, w: dict) -> list:
        """(rows, served logits) of the requests drawn for the check that
        completed, as one pair."""
        idx = sorted(w["kept"])
        if not idx:
            return []
        served = [np.asarray(w["kept"][i]) for i in idx]
        x = self.pool[w["rows_idx"][idx]]
        if any(y.shape[0] != 1 for y in served):
            return [(x, served[0])]
        return [(x, np.concatenate(served))]


def _stalls(due_off: np.ndarray, late_ms: np.ndarray, limit_ms: float):
    """[offset in the window (s), how late (ms)] of each stall: a run of
    sends more than ``limit_ms`` late, due within 0.2 s of each other."""
    out, last = [], -1.0
    for i in np.flatnonzero(late_ms > limit_ms):
        if out and due_off[i] - last < 0.2:
            out[-1][1] = max(out[-1][1], float(late_ms[i]))
        else:
            out.append([float(due_off[i]), float(late_ms[i])])
        last = due_off[i]
    return out


def _gc_summary(events) -> dict:
    """Count and longest pause (ms) of the collections of each
    generation, from ``gc.callbacks`` start/stop events."""
    out, begun = {}, None
    for phase, gen, t in events:
        if phase == "start":
            begun = t
        elif begun is not None:
            n, worst = out.get(str(gen), (0, 0.0))
            out[str(gen)] = (n + 1, max(worst, (t - begun) * 1e3))
            begun = None
    return {g: {"n": n, "max_ms": m} for g, (n, m) in sorted(out.items())}
