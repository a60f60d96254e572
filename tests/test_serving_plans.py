"""ExecutionPlan: mode/bucket resolution, entry caching, and parity of the
weight-stationary latency schedule against the batch-tiled megakernel."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serving
from repro.core import bitplanes as bp
from repro.kernels import ops


def _rand_pack(dims, seed=0):
    rng = np.random.default_rng(seed)
    layers = []
    for i, (k, n) in enumerate(zip(dims[:-1], dims[1:])):
        codes = rng.integers(0, 16, size=(k + (k % 2), n)).astype(np.uint8)
        if k % 2:
            codes[-1] = 0
        layers.append({
            "packed": bp.pack_codes_rows(jnp.asarray(codes)),
            "omega": jnp.asarray(rng.normal(size=4) / np.sqrt(k), jnp.float32),
            "alpha1": jnp.asarray(rng.normal(size=n) * 0.5, jnp.float32),
            "bias": jnp.asarray(rng.normal(size=n) * 0.1, jnp.float32),
            "alpha2": jnp.asarray(np.float32(1.0)),
            "shape": (k, n),
            "activation": "relu" if i < len(dims) - 2 else None,
        })
    return {"layers": layers, "act_bits": None}


DIMS = (33, 129, 71, 7)


def test_auto_resolves_fused_and_buckets_are_pow2():
    plan = serving.build_plan(_rand_pack(DIMS), mode="auto", interpret=True)
    d = plan.describe()
    assert d["resolved_mode"] == "fused"
    assert d["bucket_sizes"] == sorted(d["bucket_sizes"])
    assert all(b & (b - 1) == 0 for b in d["bucket_sizes"])
    assert d["bucket_sizes"][0] == 1
    assert max(d["bucket_sizes"]) <= max(d["block_m"], 1)


def test_vmem_overflow_resolves_to_per_layer_with_note():
    plan = serving.build_plan(_rand_pack(DIMS), mode="fused", interpret=True,
                              vmem_budget_bytes=1)
    d = plan.describe()
    assert d["resolved_mode"] == "per_layer"
    assert any("VMEM" in n for n in d["notes"])
    # and it still serves correctly
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, DIMS[0])),
                    jnp.float32)
    oracle = serving.build_plan(_rand_pack(DIMS), mode="oracle")
    np.testing.assert_allclose(plan.run(x), oracle.run(x),
                               atol=1e-3, rtol=1e-4)


def test_bucket_paths_ws_db_and_plain():
    plan = serving.build_plan(_rand_pack(DIMS), mode="fused", interpret=True,
                              double_buffer=True)
    paths = plan.describe()["bucket_paths"]
    assert paths[1] == "fused_ws" and paths[8] == "fused_ws"
    assert paths[16] == "fused_db"
    assert plan.path_for(9) in ("fused", "fused_db")
    # batch label reflects the resolved bucket, not the request flags
    assert "weight-stationary" in plan.mode_label(1)
    assert "double-buffered" in plan.mode_label(16)


def test_double_buffer_note_when_it_cannot_engage():
    plan = serving.build_plan(_rand_pack(DIMS), mode="per_layer",
                              interpret=True, double_buffer=True)
    assert any("double_buffer" in n for n in plan.notes)


def test_run_pads_to_bucket_and_slices_back():
    pack = _rand_pack(DIMS)
    plan = serving.build_plan(pack, mode="fused", interpret=True)
    oracle = serving.build_plan(pack, mode="oracle")
    for m in (1, 3, 5, 8, 13):
        x = jnp.asarray(np.random.default_rng(m).normal(size=(m, DIMS[0])),
                        jnp.float32)
        y = plan.run(x)
        assert y.shape == (m, DIMS[-1])
        np.testing.assert_allclose(y, oracle.run(x), atol=1e-3, rtol=1e-4)


def test_plan_run_span_and_describe_name_the_decode_form(tmp_path):
    """``serving.plan_run`` carries metadata ``decode``, and describe()
    the same field: ``once`` for a LeNet batch past the largest bucket
    (five 8-row tiles), ``per_tile`` for a bucket of one tile."""
    import glob
    import jax
    from jax.profiler import ProfileData
    plan = serving.build_plan(_rand_pack((784, 300, 100, 10)), mode="fused",
                              interpret=True, max_bucket=8, ws_bucket_rows=0)
    assert plan.describe()["bucket_paths"][8] == "fused"
    rng = np.random.default_rng(0)
    big = jnp.asarray(rng.normal(size=(40, 784)), jnp.float32)
    small = jnp.asarray(rng.normal(size=(8, 784)), jnp.float32)
    for x in (big, small):                    # compile outside the trace
        plan.run(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for x in (big, small):
            plan.run(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = ProfileData.from_file(path)
    runs = sorted((e.start_ns, dict(e.stats)["decode"])
                  for p in data.planes for line in p.lines
                  for e in line.events if e.name == "serving.plan_run")
    assert [form for _, form in runs] == ["once", "per_tile"]
    d = plan.describe()
    assert d["oversize_bindings"] == {
        40: {"path": "fused", "block_m": 8, "decode": "once"}}
    assert d["bucket_decode"][8] == "per_tile"


def test_entry_is_cached_and_shape_checked():
    plan = serving.build_plan(_rand_pack(DIMS), mode="fused", interpret=True)
    assert plan.entry(4) is plan.entry(4)
    with pytest.raises(KeyError):
        plan.entry(3)                      # not a bucket
    with pytest.raises(AssertionError):
        plan.entry(4)(jnp.zeros((5, DIMS[0]), jnp.float32))


def test_int8_calibration_happens_once_and_matches_chain():
    pack = _rand_pack(DIMS, seed=3)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(16, DIMS[0])),
                    jnp.float32)
    calib = serving.calibrate_act_scales(pack, x)
    plan = serving.build_plan(pack, mode="fused", act_dtype="int8",
                              calib=calib, interpret=True)
    y_plan = plan.run(x)
    y_chain = ops.fantastic4_mlp_chain_int8(
        x, pack["layers"], calib["act_scales"], use_kernel=True,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(y_plan), np.asarray(y_chain))
    # without calib, the plan self-calibrates on a synthetic batch + notes it
    plan2 = serving.build_plan(pack, mode="fused", act_dtype="int8",
                               interpret=True)
    assert plan2.act_scales is not None
    assert any("calibration" in n for n in plan2.notes)


def test_ws_schedule_matches_batch_tiled_megakernel():
    """The weight-stationary latency path reproduces the batch-tiled
    megakernel: allclose on fp32, bit-for-bit on the int8 grid (they share
    decode + epilogue arithmetic; only the dataflow differs)."""
    for dims in (DIMS, (512, 512, 256, 12), (47, 96, 13)):
        pack = _rand_pack(dims, seed=sum(dims))
        x = jnp.asarray(np.random.default_rng(2).normal(size=(4, dims[0])),
                        jnp.float32)
        calib = serving.calibrate_act_scales(pack, x)
        y_ws = ops.fantastic4_mlp_fused(x, pack["layers"], interpret=True,
                                        weight_stationary=True)
        y_mk = ops.fantastic4_mlp_fused(x, pack["layers"], interpret=True)
        np.testing.assert_allclose(y_ws, y_mk, atol=1e-4, rtol=1e-5)
        i_ws = ops.fantastic4_mlp_fused(
            x, pack["layers"], interpret=True, weight_stationary=True,
            act_dtype="int8", act_scales=calib["act_scales"])
        i_mk = ops.fantastic4_mlp_fused(
            x, pack["layers"], interpret=True,
            act_dtype="int8", act_scales=calib["act_scales"])
        np.testing.assert_array_equal(np.asarray(i_ws), np.asarray(i_mk),
                                      err_msg=str(dims))


def test_ws_overbudget_falls_back_to_chain():
    pack = _rand_pack(DIMS, seed=5)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, DIMS[0])),
                    jnp.float32)
    y_fb = ops.fantastic4_mlp_fused(x, pack["layers"], interpret=True,
                                    weight_stationary=True,
                                    vmem_budget_bytes=1)
    y_ch = ops.fantastic4_mlp_chain(x, pack["layers"], use_kernel=True,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(y_fb), np.asarray(y_ch))


def test_get_plan_memoizes_per_pack_and_config():
    pack = _rand_pack(DIMS)
    a = serving.get_plan(pack, mode="fused", interpret=True)
    b = serving.get_plan(pack, mode="fused", interpret=True)
    c = serving.get_plan(pack, mode="per_layer", interpret=True)
    assert a is b
    assert a is not c
    other = _rand_pack(DIMS, seed=9)
    assert serving.get_plan(other, mode="fused", interpret=True) is not a


# ------------------- autotuner v2: per-bucket schedule binding (PR 4)

def test_ws_bucket_rows_opt_out_and_explicit_cap():
    """ws_bucket_rows=0 opts the ws schedule out entirely; an explicit
    positive value caps its eligibility at that row count."""
    plan0 = serving.build_plan(_rand_pack(DIMS), mode="fused",
                               interpret=True, ws_bucket_rows=0)
    assert not any(p == "fused_ws"
                   for p in plan0.describe()["bucket_paths"].values())
    plan2 = serving.build_plan(_rand_pack(DIMS), mode="fused",
                               interpret=True, ws_bucket_rows=2)
    paths = plan2.describe()["bucket_paths"]
    assert paths[1] == "fused_ws" and paths[2] == "fused_ws"
    assert paths[4] == "fused"


def test_measured_crossover_replaces_constant_prior(tmp_path, monkeypatch):
    """A persisted ws crossover for this backend+stack becomes the plan's
    prior: the WS_BUCKET_ROWS constant only answers when nothing was ever
    measured."""
    from repro.kernels import autotune
    monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "cache.json"))
    autotune.clear_memory_cache()
    try:
        pack = _rand_pack(DIMS, seed=12)
        plan = serving.build_plan(pack, mode="fused", interpret=True)
        d = plan.describe()
        assert d["ws_prior_source"] == "constant"
        assert d["ws_prior_rows"] == serving.plans.WS_BUCKET_ROWS
        assert d["bucket_schedules"][8] == "ws"

        autotune.record_ws_crossover(2, DIMS[0], DIMS[-1],
                                     backend="interpret",
                                     stack="stack129x71x7")
        plan2 = serving.build_plan(pack, mode="fused", interpret=True)
        d2 = plan2.describe()
        assert d2["ws_prior_source"] == "measured"
        assert d2["ws_prior_rows"] == 2
        assert d2["bucket_schedules"][1] == "ws"
        assert d2["bucket_schedules"][2] == "ws"
        assert d2["bucket_schedules"][4] == "batch_tiled"
        assert d2["ws_crossover_rows"] == 2
    finally:
        autotune.clear_memory_cache()


def test_opt_out_plan_never_records_a_crossover(tmp_path, monkeypatch):
    """A ws-opt-out (or capped) plan's bucket table reflects the caller's
    restriction, not a measurement — it must not write a 'measured'
    crossover that future default plans would trust."""
    from repro.kernels import autotune
    from repro.kernels.autotune import BlockConfig
    from repro.serving import plans as plans_mod
    monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "cache.json"))
    autotune.clear_memory_cache()
    monkeypatch.setattr(
        plans_mod.autotune, "get_schedule_config",
        lambda rows, k, n, *, schedules, prior, **kw: BlockConfig(
            min(8, rows), 0, 0, source="sweep", schedule=prior))
    try:
        pack = _rand_pack(DIMS, seed=17)
        # interpret=False exercises the recording branch; the fake tuner
        # keeps real kernels out of the non-interpret path.
        plan = serving.build_plan(pack, mode="fused", interpret=False,
                                  ws_bucket_rows=0, block_m=32)
        assert plan.ws_crossover_rows == 0
        assert autotune.get_ws_crossover(
            DIMS[0], DIMS[-1], backend="cpu",
            stack="stack129x71x7") is None, \
            "opt-out plan must not persist a crossover"
        plan2 = serving.build_plan(pack, mode="fused", interpret=False,
                                   block_m=32)
        assert autotune.get_ws_crossover(
            DIMS[0], DIMS[-1], backend="cpu",
            stack="stack129x71x7") == plan2.ws_crossover_rows
    finally:
        autotune.clear_memory_cache()


def test_plans_bind_measured_per_bucket_winners(monkeypatch):
    """ExecutionPlan consumes whatever the per-bucket tuner returns — a
    measured 'stream wins the mid buckets' table binds fused_stream
    entries whose per-bucket block_m reaches the kernel, and serving
    through them stays correct."""
    from repro.kernels.autotune import BlockConfig
    from repro.serving import plans as plans_mod

    calls = []

    def fake_schedule_config(rows, k, n, *, schedules, prior, **kw):
        calls.append((rows, tuple(schedules), prior))
        sched = "stream" if rows >= 16 else "ws"
        if sched not in schedules:
            sched = prior
        return BlockConfig(min(8, rows), 0, 0, source="sweep",
                           schedule=sched)

    monkeypatch.setattr(plans_mod.autotune, "get_schedule_config",
                        fake_schedule_config)
    pack = _rand_pack(DIMS, seed=13)
    plan = serving.build_plan(pack, mode="fused", interpret=True)
    d = plan.describe()
    assert calls and all(rows in plan.bucket_sizes for rows, _, _ in calls)
    assert d["bucket_schedules"][1] == "ws"
    assert d["bucket_schedules"][16] == "stream"
    assert d["bucket_sources"][16] == "sweep"
    assert d["bucket_block_m"][16] == 8     # per-bucket tile, not global
    assert d["ws_crossover_rows"] == 8      # largest ws-bound bucket
    assert "streaming" in plan.mode_label(16)
    assert plan.schedule_for(16) == "stream"
    # the stream binding serves correctly (block_m=8 -> 2 tiles at b=16)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(16, DIMS[0])),
                    jnp.float32)
    oracle = serving.build_plan(pack, mode="oracle")
    np.testing.assert_allclose(plan.run(x), oracle.run(x),
                               atol=1e-3, rtol=1e-4)


def test_stream_rescues_stack_too_big_for_batch_tiled():
    """A stack whose *total* working set busts the batch-tiled budget but
    whose per-layer streamed set fits resolves to fused with stream
    buckets instead of dropping all the way to per_layer."""
    from repro.kernels.fantastic4_fused_mlp import (fused_mlp_vmem_bytes,
                                                    stream_mlp_vmem_bytes)
    dims = (256,) * 7
    pack = _rand_pack(dims, seed=21)
    shapes = tuple(zip(dims[:-1], dims[1:]))
    stack_b = fused_mlp_vmem_bytes(shapes, block_m=256)
    stream_b = stream_mlp_vmem_bytes(shapes, rows=256, block_m=256)
    assert stream_b < stack_b, "test premise: stream must be the smaller set"
    budget = (stream_b + stack_b) // 2
    plan = serving.build_plan(pack, mode="auto", interpret=True,
                              vmem_budget_bytes=budget)
    d = plan.describe()
    assert d["resolved_mode"] == "fused"
    assert any("layer-streamed" in n for n in d["notes"])
    assert d["bucket_schedules"][32] == "stream"
    assert d["default_path"] == "per_layer"   # past the largest bucket
    x = jnp.asarray(np.random.default_rng(6).normal(size=(32, dims[0])),
                    jnp.float32)
    oracle = serving.build_plan(pack, mode="oracle")
    np.testing.assert_allclose(plan.run(x), oracle.run(x),
                               atol=1e-3, rtol=1e-4)


def test_overflow_default_path_honors_double_buffer():
    """Batches past the largest bucket run at exact size; a requested
    double buffer must reach them (it did before per-bucket binding)."""
    plan = serving.build_plan(_rand_pack(DIMS), mode="fused",
                              interpret=True, double_buffer=True,
                              max_bucket=16)
    assert plan.default_path == "fused_db"
    assert plan.path_for(64) == "fused_db"
    plain = serving.build_plan(_rand_pack(DIMS), mode="fused",
                               interpret=True, max_bucket=16)
    assert plain.default_path == "fused"


def test_schedule_measure_fit_guards_candidates():
    """The sweep's measure closure returns inf for a (schedule, block_m)
    candidate whose working set busts the budget — otherwise the kernel
    wrapper's silent chain fallback could win the timing and the bucket
    would carry a fused label over per-layer execution."""
    from repro.kernels.fantastic4_fused_mlp import stream_mlp_vmem_bytes
    dims = (256,) * 7
    pack = _rand_pack(dims, seed=23)
    shapes = tuple(zip(dims[:-1], dims[1:]))
    lo = stream_mlp_vmem_bytes(shapes, rows=256, block_m=8)
    hi = stream_mlp_vmem_bytes(shapes, rows=256, block_m=256)
    assert lo < hi
    plan = serving.build_plan(pack, mode="auto", interpret=True,
                              vmem_budget_bytes=(lo + hi) // 2)
    measure = plan._schedule_measure(256)
    assert measure("stream", 256) == float("inf")
    assert measure("stream", 8) < float("inf")


def test_stream_entry_matches_batch_tiled_bitwise_int8():
    """The engine-facing contract behind re-binding a bucket to stream:
    on the int8 grid the streaming schedule is bit-identical to the
    batch-tiled megakernel, so a measured re-bind can never change
    results."""
    pack = _rand_pack((512, 512, 256, 12), seed=4)
    x = jnp.asarray(np.random.default_rng(9).normal(size=(48, 512)),
                    jnp.float32)
    calib = serving.calibrate_act_scales(pack, x)
    i_stream = ops.fantastic4_mlp_fused(
        x, pack["layers"], interpret=True, schedule="stream", block_m=16,
        act_dtype="int8", act_scales=calib["act_scales"])
    i_mk = ops.fantastic4_mlp_fused(
        x, pack["layers"], interpret=True,
        act_dtype="int8", act_scales=calib["act_scales"])
    np.testing.assert_array_equal(np.asarray(i_stream), np.asarray(i_mk))


def test_compat_wrappers_flow_through_plans():
    """mlp_serve/mlp_serve_int8 are thin shims over ExecutionPlan now —
    same results, no mode keywords reaching the kernels directly."""
    from repro.models import mlp as M
    pack = _rand_pack(DIMS, seed=8)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(5, DIMS[0])),
                    jnp.float32)
    plan = serving.build_plan(pack, mode="fused", interpret=True,
                              ws_bucket_rows=0)
    np.testing.assert_array_equal(
        np.asarray(M.mlp_serve(pack, x, interpret=True)),
        np.asarray(plan.run(x)))


def test_failed_sweep_candidate_is_a_plan_note(monkeypatch):
    """On a backend that sweeps, a (schedule, block_m) candidate that fails
    to compile is reported in the plan's notes with its error and never
    bound; the other candidates still bind."""
    from repro.kernels import autotune
    real = ops.fantastic4_mlp_fused

    def fake_fused(x, layers, *, schedule=None, **kw):
        if schedule == "db":
            raise NotImplementedError("Unsupported cast: uint8 -> float32")
        return real(x, layers, schedule=schedule, **{**kw,
                                                     "interpret": True})

    monkeypatch.setattr(ops, "fantastic4_mlp_fused", fake_fused)
    with autotune.collect_failures([]) as seen:
        plan = serving.build_plan(_rand_pack(DIMS), mode="fused",
                                  interpret=False, max_bucket=32)
    d = plan.describe()
    failed = [n for n in d["notes"] if n.startswith("sweep candidate failed")]
    assert failed and all("Unsupported cast" in n for n in failed)
    assert failed == seen
    assert "fused_db" not in d["bucket_paths"].values()
    assert {d["bucket_sources"][b] for b in d["bucket_sizes"]} == {"sweep"}
