"""Autotuner: cache round-trip (cold sweep -> JSON persist -> warm hit),
heuristic shape-clamping, and the ops-level None-block integration."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplanes as bp
from repro.kernels import autotune, ops, ref


@pytest.fixture
def tuner_cache(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.ENV_CACHE, str(path))
    autotune.clear_memory_cache()
    yield path
    autotune.clear_memory_cache()


def test_cold_sweep_persists_and_warm_hit_skips_measure(tuner_cache):
    measured = []

    def fake_measure(cfg):
        measured.append(cfg)
        # prefer the largest block_k, then largest block_n, smallest block_m
        return 1.0 / (cfg.block_k * 1e6 + cfg.block_n * 1e3 + 1.0 / cfg.block_m)

    cold = autotune.get_block_config(64, 512, 256, dtype="float32",
                                     fused=False, backend="tpu",
                                     measure=fake_measure)
    assert measured, "cold call must run the sweep"
    assert cold.source == "sweep"
    assert os.path.exists(tuner_cache)
    raw = json.loads(tuner_cache.read_text())
    key = autotune.cache_key(64, 512, 256, dtype="float32", fused=False,
                             backend="tpu")
    assert raw[key]["block_m"] == cold.block_m

    # fresh process analogue: drop the memory cache, keep the JSON
    autotune.clear_memory_cache()
    measured2 = []
    warm = autotune.get_block_config(64, 512, 256, dtype="float32",
                                     fused=False, backend="tpu",
                                     measure=lambda c: measured2.append(c) or 0.0)
    assert not measured2, "warm hit must not re-measure"
    assert warm.same_blocks(cold)


def test_distinct_keys_do_not_collide(tuner_cache):
    a = autotune.get_block_config(8, 512, 256, dtype="float32", fused=False,
                                  backend="cpu")
    b = autotune.get_block_config(256, 512, 256, dtype="float32", fused=False,
                                  backend="cpu")
    c = autotune.get_block_config(8, 512, 256, dtype="float32", fused=True,
                                  backend="cpu")
    raw = json.loads(tuner_cache.read_text())
    assert len(raw) == 3
    assert a.block_m <= 8 or a.block_m == 8  # clamped to padded batch
    assert b.block_m >= a.block_m
    assert c is not None


def test_fused_stacks_with_same_ends_get_distinct_keys(tuner_cache):
    """MLP-GSC and MLP-HR share (M, K0=512, N_last=12); the fused cache key
    must still tell them apart via the hidden-width extra."""
    a = autotune.cache_key(64, 512, 12, dtype="float32", fused=True,
                           backend="tpu", extra="stack512x512x256x12")
    b = autotune.cache_key(64, 512, 12, dtype="float32", fused=True,
                           backend="tpu", extra="stack512x256x128x12")
    assert a != b
    autotune.get_block_config(64, 512, 12, dtype="float32", fused=True,
                              backend="tpu", extra="stack512x512x256x12")
    autotune.get_block_config(64, 512, 12, dtype="float32", fused=True,
                              backend="tpu", extra="stack512x256x128x12")
    raw = json.loads(tuner_cache.read_text())
    assert len(raw) == 2


def test_interpret_mode_does_not_poison_backend_key(tuner_cache):
    """Interpret-mode resolution (backend="interpret") must not occupy the
    real backend's cache slot, or the TPU timed sweep would never run."""
    autotune.get_block_config(64, 512, 256, dtype="float32", fused=False,
                              backend="interpret")
    measured = []
    swept = autotune.get_block_config(64, 512, 256, dtype="float32",
                                      fused=False, backend="tpu",
                                      measure=lambda c: measured.append(c)
                                      or 1.0)
    assert measured, "tpu-key resolution must still sweep"
    assert swept.source == "sweep"


def test_act_dtype_distinguishes_entries(tuner_cache):
    """The int8 fused kernel has a different body (per-layer quantize);
    its tuned blocks must not share a slot with the fp32 sweep."""
    a = autotune.cache_key(64, 512, 12, dtype="float32", fused=True,
                           backend="tpu", act_dtype="float32")
    b = autotune.cache_key(64, 512, 12, dtype="float32", fused=True,
                           backend="tpu", act_dtype="int8")
    assert a != b
    autotune.get_block_config(64, 512, 12, dtype="float32", fused=True,
                              backend="tpu", act_dtype="float32")
    autotune.get_block_config(64, 512, 12, dtype="float32", fused=True,
                              backend="tpu", act_dtype="int8")
    raw = json.loads(tuner_cache.read_text())
    assert len(raw) == 2


def test_stale_pre_act_dtype_cache_is_migrated(tuner_cache):
    """A PR-1-era JSON (keys without the act segment) must load cleanly:
    its entries resurface under act_dtype=float32 instead of crashing or
    being re-swept."""
    old_key = "tpu|m64|k512|n256|float32|fused0"
    old_fused = "tpu|m64|k512|n12|float32|fused1|stack512x256x12"
    tuner_cache.write_text(json.dumps({
        old_key: {"block_m": 32, "block_n": 128, "block_k": 256,
                  "source": "sweep"},
        old_fused: {"block_m": 64, "block_n": 1024, "block_k": 2048,
                    "source": "sweep"},
        "corrupt-entry": {"nope": 1},          # ignored, not fatal
    }))
    autotune.clear_memory_cache()
    measured = []
    cfg = autotune.get_block_config(64, 512, 256, dtype="float32",
                                    fused=False, backend="tpu",
                                    measure=lambda c: measured.append(c)
                                    or 1.0)
    assert not measured, "migrated entry must hit, not re-sweep"
    assert cfg.as_tuple() == (32, 128, 256)
    cfg2 = autotune.get_block_config(64, 512, 12, dtype="float32",
                                     fused=True, backend="tpu",
                                     extra="stack512x256x12",
                                     measure=lambda c: measured.append(c)
                                     or 1.0)
    assert not measured
    assert cfg2.as_tuple() == (64, 1024, 2048)
    # int8 lookups for the same shape/backend do NOT inherit the migrated
    # fp32 entry: the sweep must run afresh
    int8_measured = []
    int8_cfg = autotune.get_block_config(
        64, 512, 12, dtype="float32", fused=True, backend="tpu",
        act_dtype="int8", extra="stack512x256x12",
        measure=lambda c: int8_measured.append(c) or 1.0)
    assert int8_measured, "int8 key must not hit the migrated fp32 entry"
    assert int8_cfg.source == "sweep"


def test_migrate_key_roundtrip():
    new = autotune.cache_key(8, 16, 32, dtype="float32", fused=True,
                             backend="cpu", act_dtype="int8", extra="e")
    assert autotune._migrate_key(new) == new       # current format: no-op
    old = "cpu|m8|k16|n32|float32|fused1|e"
    assert autotune._migrate_key(old) == \
        "cpu|m8|k16|n32|float32|fused1|actfloat32|e"


def test_interpret_mode_act_dtype_keys_do_not_mask_backend(tuner_cache):
    """Interpret-mode int8 answers stay under backend="interpret" — the
    real backend's int8 sweep must still run later."""
    autotune.get_block_config(64, 512, 12, dtype="float32", fused=True,
                              backend="interpret", act_dtype="int8")
    measured = []
    swept = autotune.get_block_config(64, 512, 12, dtype="float32",
                                      fused=True, backend="tpu",
                                      act_dtype="int8",
                                      measure=lambda c: measured.append(c)
                                      or 1.0)
    assert measured, "tpu int8 key must still sweep"
    assert swept.source == "sweep"


def test_heuristic_clamps_to_problem_dims():
    cfg = autotune.heuristic_blocks(1, 784, 12, backend="tpu")
    assert cfg.block_m == 8               # batch 1 -> one f32 sublane tile
    assert cfg.block_n == 128             # 12 -> one lane tile, not 256
    assert cfg.block_k <= 896
    big = autotune.heuristic_blocks(4096, 4096, 4096, backend="tpu")
    assert big.as_tuple() == (128, 256, 512)  # falls back to seed defaults


def test_failed_candidates_fall_back_to_heuristic(tuner_cache):
    cfg = autotune.get_block_config(16, 64, 64, dtype="float32", fused=False,
                                    backend="tpu",
                                    measure=lambda c: float("inf"))
    assert cfg.source == "heuristic"


def test_elementwise_cache_key_distinct_from_matmul(tuner_cache):
    """ecl_quant's (block_r, block_c) entries live under k=0 + an op extra:
    they must never collide with a matmul shape's blocks (satellite
    cache-key contract)."""
    ew = autotune.cache_key(256, 0, 512, dtype="float32", fused=False,
                            backend="tpu", extra="eclquant")
    mm = autotune.cache_key(256, 0, 512, dtype="float32", fused=False,
                            backend="tpu")
    assert ew != mm
    autotune.get_elementwise_config(256, 512, backend="tpu")
    autotune.get_block_config(256, 0, 512, dtype="float32", fused=False,
                              backend="tpu")
    raw = json.loads(tuner_cache.read_text())
    assert len(raw) == 2
    assert ew in raw


def test_elementwise_cold_sweep_persists_and_warm_hit(tuner_cache):
    measured = []

    def fake_measure(cfg):
        measured.append(cfg)
        return 1.0 / (cfg.block_m * 1e3 + cfg.block_n)

    cold = autotune.get_elementwise_config(300, 700, backend="tpu",
                                           measure=fake_measure)
    assert measured and cold.source == "sweep"
    assert cold.block_k == 0               # elementwise sentinel
    autotune.clear_memory_cache()
    warm = autotune.get_elementwise_config(
        300, 700, backend="tpu",
        measure=lambda c: measured.append(("again", c)) or 0.0)
    assert not any(isinstance(m, tuple) for m in measured), \
        "warm hit must not re-measure"
    assert warm.same_blocks(cold)


def test_elementwise_heuristic_clamps_to_problem():
    cfg = autotune.heuristic_elementwise_blocks(5, 30, backend="tpu")
    assert cfg.block_m == 8 and cfg.block_n == 128
    big = autotune.heuristic_elementwise_blocks(4096, 4096, backend="tpu")
    assert 9 * big.block_m * big.block_n <= 4 << 20


def test_ecl_quant_autotuned_blocks_match_ref(tuner_cache):
    """ops.ecl_quant with block_r/block_c=None (the new default) resolves
    via the autotuner and stays bit-accurate vs the oracle."""
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(100, 30)), jnp.float32)
    omega = jnp.asarray(rng.normal(size=4) * 0.3, jnp.float32)
    probs = jnp.asarray(rng.dirichlet(np.ones(16)), jnp.float32)
    penalty = 0.05 * -jnp.log2(jnp.clip(probs, 1e-8, 1.0))
    ck, wk = ops.ecl_quant(w, omega, penalty, use_kernel=True,
                           interpret=True)
    cr, wr = ref.ecl_quant_ref(w, omega, penalty)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
    np.testing.assert_allclose(wk, wr, atol=1e-5)
    raw = json.loads(tuner_cache.read_text())
    assert any("eclquant" in k for k in raw), \
        "interpret-mode resolution must land under the eclquant key"


# --------------------------- autotuner v2: (bucket, schedule) tuning unit

def test_schedule_sweep_picks_winner_and_persists(tuner_cache):
    """Cold per-bucket sweep measures every eligible (schedule, block_m)
    pair, binds the fastest, persists it with its schedule field; the warm
    hit (fresh process analogue) never re-measures."""
    seen = []

    def fake_measure(sched, bm):
        seen.append((sched, bm))
        return {"stream": 1.0, "batch_tiled": 2.0,
                "db": 3.0, "ws": 4.0}[sched] + 1e-3 / bm

    cold = autotune.get_schedule_config(
        32, 512, 12, schedules=("batch_tiled", "db", "stream", "ws"),
        prior="batch_tiled", backend="tpu", stack="stack512x12",
        measure=fake_measure)
    assert seen, "cold call must sweep"
    assert {s for s, _ in seen} == {"batch_tiled", "db", "stream", "ws"}
    assert cold.schedule == "stream" and cold.source == "sweep"
    # ws holds the whole bucket: exactly one candidate, block_m = padded rows
    assert [bm for s, bm in seen if s == "ws"] == [32]
    # db tiles need two sublane groups: candidates stay multiples of 16
    assert all(bm % 16 == 0 for s, bm in seen if s == "db")

    raw = json.loads(tuner_cache.read_text())
    key = autotune.bucket_cache_key(32, 512, 12, backend="tpu",
                                    stack="stack512x12")
    assert raw[key]["schedule"] == "stream"

    autotune.clear_memory_cache()
    warm = autotune.get_schedule_config(
        32, 512, 12, schedules=("batch_tiled", "db", "stream", "ws"),
        prior="batch_tiled", backend="tpu", stack="stack512x12",
        measure=lambda s, bm: seen.append(("again", s)) or 0.0)
    assert not any(s == "again" for s, _ in seen), "warm hit re-measured"
    assert warm.schedule == "stream" and warm.same_blocks(cold)


def test_schedule_entries_keyed_per_bucket(tuner_cache):
    """Bucket 8 and bucket 32 are distinct tuning units — the whole point
    of v2 — and neither collides with the legacy single fused entry."""
    a = autotune.bucket_cache_key(8, 512, 12, backend="tpu",
                                  stack="stack512x12")
    b = autotune.bucket_cache_key(32, 512, 12, backend="tpu",
                                  stack="stack512x12")
    legacy = autotune.cache_key(8, 512, 12, dtype="float32", fused=True,
                                backend="tpu", extra="stack512x12")
    assert len({a, b, legacy}) == 3
    for rows in (8, 32):
        autotune.get_schedule_config(
            rows, 512, 12, schedules=("batch_tiled", "ws"), prior="ws",
            backend="tpu", stack="stack512x12",
            measure=lambda s, bm: 1.0 if s == "ws" else 2.0)
    raw = json.loads(tuner_cache.read_text())
    assert len(raw) == 2 and a in raw and b in raw


def test_schedule_prior_answers_without_measure_and_is_not_cached(
        tuner_cache):
    """Interpret tier: the prior answers, block_m falls back to the
    heuristic — and the answer must NOT enter the cache (priors depend on
    the caller's eligibility/requests; caching one plan's prior would
    shadow another plan's, and would mask a future real sweep)."""
    cfg = autotune.get_schedule_config(
        4, 512, 12, schedules=("batch_tiled", "ws"), prior="ws",
        backend="interpret", stack="stack512x12")
    assert cfg.schedule == "ws" and cfg.source == "heuristic"
    assert not os.path.exists(tuner_cache) or \
        autotune.bucket_cache_key(4, 512, 12, backend="interpret",
                                  stack="stack512x12") \
        not in json.loads(tuner_cache.read_text())
    # a different caller's restricted eligibility gets ITS prior, not the
    # first caller's answer
    cfg2 = autotune.get_schedule_config(
        4, 512, 12, schedules=("batch_tiled",), prior="batch_tiled",
        backend="interpret", stack="stack512x12")
    assert cfg2.schedule == "batch_tiled"


def test_schedule_migrates_legacy_single_entry_block(tuner_cache):
    """An old cache file holds one fused entry tuned at the largest bucket
    (m=256).  Per-bucket resolution without a measure must migrate its
    block_m (clamped to the bucket) instead of discarding it."""
    legacy_key = autotune.cache_key(256, 512, 12, dtype="float32",
                                    fused=True, backend="tpu",
                                    extra="stack512x12")
    tuner_cache.write_text(json.dumps({
        legacy_key: {"block_m": 64, "block_n": 1024, "block_k": 2048,
                     "source": "sweep"}}))
    autotune.clear_memory_cache()
    cfg = autotune.get_schedule_config(
        8, 512, 12, schedules=("batch_tiled", "ws"), prior="batch_tiled",
        backend="tpu", stack="stack512x12", legacy_m=256)
    assert cfg.source == "migrated"
    assert cfg.block_m == 8                 # min(legacy 64, padded rows 8)
    cfg2 = autotune.get_schedule_config(
        128, 512, 12, schedules=("batch_tiled",), prior="batch_tiled",
        backend="tpu", stack="stack512x12", legacy_m=256)
    assert cfg2.source == "migrated" and cfg2.block_m == 64
    # the legacy entry itself survives a later save untouched
    autotune.record_ws_crossover(8, 512, 12, backend="tpu",
                                 stack="stack512x12")
    raw = json.loads(tuner_cache.read_text())
    assert raw[legacy_key]["block_m"] == 64
    assert "schedule" not in raw[legacy_key]


def test_cached_schedule_outside_eligibility_is_bypassed_not_clobbered(
        tuner_cache):
    """A measured ws binding must survive a ws-opt-out caller: the
    restricted resolution answers from the prior but leaves the cache
    entry alone."""
    swept = autotune.get_schedule_config(
        2, 512, 12, schedules=("batch_tiled", "ws"), prior="batch_tiled",
        backend="tpu", stack="stack512x12",
        measure=lambda s, bm: 1.0 if s == "ws" else 2.0)
    assert swept.schedule == "ws"
    restricted = autotune.get_schedule_config(
        2, 512, 12, schedules=("batch_tiled",), prior="batch_tiled",
        backend="tpu", stack="stack512x12")
    assert restricted.schedule == "batch_tiled"
    key = autotune.bucket_cache_key(2, 512, 12, backend="tpu",
                                    stack="stack512x12")
    assert json.loads(tuner_cache.read_text())[key]["schedule"] == "ws"


def test_schedule_entries_keyed_per_act_dtype_and_backend(tuner_cache):
    keys = {autotune.bucket_cache_key(8, 512, 12, backend=b,
                                      act_dtype=a, stack="s")
            for b in ("tpu", "interpret") for a in ("float32", "int8")}
    assert len(keys) == 4


def test_restricted_sweep_does_not_shadow_broader_eligibility(tuner_cache):
    """A ws-opt-out plan sweeping FIRST must not pin the bucket for later
    default plans: the entry records the set it measured over, and a
    caller with broader eligibility re-sweeps (and its complete entry then
    serves both)."""
    first = autotune.get_schedule_config(
        2, 512, 12, schedules=("batch_tiled", "stream"),
        prior="batch_tiled", backend="tpu", stack="s",
        measure=lambda s, bm: {"batch_tiled": 1.0, "stream": 2.0,
                               "ws": 0.5}[s])
    assert first.schedule == "batch_tiled"
    assert first.swept == ("batch_tiled", "stream")
    # broader caller: ws (never measured above) must get its sweep
    full = autotune.get_schedule_config(
        2, 512, 12, schedules=("batch_tiled", "stream", "ws"),
        prior="batch_tiled", backend="tpu", stack="s",
        measure=lambda s, bm: {"batch_tiled": 1.0, "stream": 2.0,
                               "ws": 0.5}[s])
    assert full.schedule == "ws"
    # the complete entry now answers the restricted caller's *bypass*
    # path (ws forbidden -> recompute, uncached) and the full caller's hit
    again = autotune.get_schedule_config(
        2, 512, 12, schedules=("batch_tiled", "stream", "ws"),
        prior="batch_tiled", backend="tpu", stack="s",
        measure=lambda s, bm: (_ for _ in ()).throw(AssertionError))
    assert again.schedule == "ws"
    key = autotune.bucket_cache_key(2, 512, 12, backend="tpu", stack="s")
    assert set(json.loads(tuner_cache.read_text())[key]["swept"]) == \
        {"batch_tiled", "stream", "ws"}


def test_incomparable_sweep_sets_converge_via_union(tuner_cache):
    """Two plans with incomparable eligible sets must not ping-pong
    re-sweeps: the second sweep covers the union, the stored entry then
    answers both."""
    times = {"batch_tiled": 2.0, "ws": 1.0, "stream": 3.0, "db": 4.0}
    calls = []

    def measure(s, bm):
        calls.append(s)
        return times[s]

    a = autotune.get_schedule_config(
        2, 512, 12, schedules=("batch_tiled", "ws"), prior="batch_tiled",
        backend="tpu", stack="s", measure=measure)
    assert a.schedule == "ws"
    calls.clear()
    b = autotune.get_schedule_config(
        2, 512, 12, schedules=("batch_tiled", "stream"),
        prior="batch_tiled", backend="tpu", stack="s", measure=measure)
    # caller B may not bind ws, so it gets its own best...
    assert b.schedule == "batch_tiled"
    # ...but the union sweep measured ws too and stored the union winner
    assert "ws" in calls
    key = autotune.bucket_cache_key(2, 512, 12, backend="tpu", stack="s")
    raw = json.loads(tuner_cache.read_text())[key]
    assert raw["schedule"] == "ws"
    assert set(raw["swept"]) == {"batch_tiled", "ws", "stream"}
    # caller A now hits without re-sweeping: convergence, no ping-pong
    a2 = autotune.get_schedule_config(
        2, 512, 12, schedules=("batch_tiled", "ws"), prior="batch_tiled",
        backend="tpu", stack="s",
        measure=lambda s, bm: (_ for _ in ()).throw(AssertionError))
    assert a2.schedule == "ws"


def test_record_ws_crossover_first_touch_keeps_existing_file(tuner_cache):
    """record_ws_crossover in a fresh process (nothing loaded yet) must
    merge with the on-disk cache, not clobber a committed TPU cache."""
    autotune.get_schedule_config(
        8, 512, 12, schedules=("batch_tiled", "ws"), prior="ws",
        backend="tpu", stack="s", measure=lambda s, bm: 1.0)
    autotune.clear_memory_cache()            # fresh-process analogue
    autotune.record_ws_crossover(4, 512, 12, backend="tpu", stack="s")
    raw = json.loads(tuner_cache.read_text())
    assert autotune.bucket_cache_key(8, 512, 12, backend="tpu",
                                     stack="s") in raw
    assert autotune.get_ws_crossover(512, 12, backend="tpu",
                                     stack="s") == 4


def test_ws_crossover_roundtrip(tuner_cache):
    assert autotune.get_ws_crossover(512, 12, backend="tpu",
                                     stack="stack512x12") is None
    autotune.record_ws_crossover(16, 512, 12, backend="tpu",
                                 stack="stack512x12")
    assert autotune.get_ws_crossover(512, 12, backend="tpu",
                                     stack="stack512x12") == 16
    # fresh process analogue: survives via the JSON file
    autotune.clear_memory_cache()
    assert autotune.get_ws_crossover(512, 12, backend="tpu",
                                     stack="stack512x12") == 16
    # other backends/stacks unaffected
    assert autotune.get_ws_crossover(512, 12, backend="cpu",
                                     stack="stack512x12") is None
    assert autotune.get_ws_crossover(512, 12, backend="tpu",
                                     stack="stack256x12") is None


def test_schedule_failed_sweep_falls_back_to_prior(tuner_cache):
    cfg = autotune.get_schedule_config(
        8, 64, 64, schedules=("batch_tiled", "ws"), prior="ws",
        backend="tpu", stack="s", measure=lambda s, bm: float("inf"))
    assert cfg.schedule == "ws" and cfg.source == "heuristic"


def test_ops_autotuned_blocks_match_ref(tuner_cache):
    """fantastic4_matmul with block_*=None (autotuned) stays bit-accurate."""
    rng = np.random.default_rng(0)
    m, k, n = 5, 130, 72
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, 16, size=(k, n)), jnp.uint8)
    packed = bp.pack_codes_rows(codes)
    omega = jnp.asarray(rng.normal(size=4) * 0.2, jnp.float32)
    y_k = ops.fantastic4_matmul(x, packed, omega, use_kernel=True,
                                interpret=True, out_dtype=jnp.float32)
    y_r = ref.fantastic4_matmul_ref(x, packed, omega, out_dtype=jnp.float32)
    np.testing.assert_allclose(y_k, y_r, atol=1e-4, rtol=1e-4)


def test_sweep_records_a_failing_candidate_and_binds_the_rest(tuner_cache):
    """A candidate that raises is recorded with its error, never dropped
    in silence, and the sweep binds the best of the others."""
    def measure(s, bm):
        if s == "db":
            raise NotImplementedError("Unsupported cast: uint8 -> float32")
        return 1.0 if s == "batch_tiled" else 2.0

    with autotune.collect_failures([]) as new:
        cfg = autotune.get_schedule_config(
            32, 64, 64, schedules=("batch_tiled", "db", "ws"),
            prior="batch_tiled", backend="tpu", stack="s", measure=measure)
    assert cfg.schedule == "batch_tiled" and cfg.source == "sweep"
    assert new and all(m.startswith("sweep candidate failed")
                       and "'db'" in m and "Unsupported cast" in m
                       for m in new)


def test_sweep_failures_stay_with_their_thread(tuner_cache):
    """Each collector sees the failures of the sweeps its own thread ran,
    so two plans built at once never get each other's notes; a failure
    with no collector open is a warning."""
    import threading

    def failing(tag):
        def measure(cfg):
            if cfg.block_m == 32:
                raise RuntimeError(f"refused in {tag}")
            return 1.0
        return measure

    got = {}

    def build(tag, k):
        with autotune.collect_failures([]) as sink:
            autotune.get_block_config(64, k, 64, backend="tpu",
                                      measure=failing(tag))
        got[tag] = sink

    threads = [threading.Thread(target=build, args=(t, k))
               for t, k in (("a", 64), ("b", 128))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for tag in ("a", "b"):
        assert got[tag] and all(f"refused in {tag}" in m for m in got[tag])
    with pytest.warns(RuntimeWarning, match="refused in c"):
        autotune.get_block_config(64, 256, 64, backend="tpu",
                                  measure=failing("c"))


def test_sweep_raises_when_every_candidate_fails(tuner_cache):
    """No measurement at all is an error, not a silent heuristic binding
    persisted under the real backend's key."""
    def measure(cfg):
        raise RuntimeError("Mosaic refused the kernel")

    with pytest.raises(RuntimeError, match="every sweep candidate failed"):
        autotune.get_block_config(16, 64, 64, backend="tpu",
                                  measure=measure)
    assert not tuner_cache.exists()


def test_timeit_refuses_to_time_a_trace():
    """block_until_ready returns at once on a tracer, so a sweep under jit
    would time the trace: it raises instead."""
    import jax

    @jax.jit
    def traced(x):
        ops._timeit(lambda: x + 1.0)
        return x

    with pytest.raises(TypeError, match="under a trace"):
        traced(jnp.ones(()))


@pytest.mark.parametrize("backend,expected", [("cpu", True), ("tpu", False),
                                              ("gpu", None)])
def test_interpret_only_on_cpu(monkeypatch, backend, expected):
    """Interpret mode is chosen on the CPU alone; a backend with no Pallas
    path here is refused instead of quietly interpreted."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expected is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops.default_interpret()
    else:
        assert ops.default_interpret() is expected
