"""Scale-out serving: column-sharded plans over a ('data','model') mesh,
replicated execution streams (replay + threaded frontend), the serving-pack
partition rules behind both, and fit_mesh."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import REPO, run_with_devices
from repro import serving
from repro.launch.mesh import fit_mesh
from repro.runtime.sharding import Rules, serving_pack_specs
from test_serving_plans import _rand_pack

# layer widths 12 / 7 / 6 on a model=2 axis: split, replicated (odd),
# split — the divisibility fallback inside one stack.
DIMS = (16, 12, 7, 6)


# ---------------------------------------------------------------- rules

def test_serving_pack_specs_column_rule_and_fallbacks():
    pack = _rand_pack(DIMS)
    rules = Rules(("data", "model"), {"data": 2, "model": 2}, None)
    specs = serving_pack_specs(pack["layers"], rules)
    # divisible widths: Megatron column split over the output features,
    # epilogue vectors follow their layer's slice
    for i in (0, 2):
        assert specs[i]["packed"] == P(None, "model")
        assert specs[i]["alpha1"] == P("model")
        assert specs[i]["bias"] == P("model")
    # width 7 does not divide by model=2: whole layer replicates
    assert specs[1]["packed"] == P(None, None)
    assert specs[1]["alpha1"] == P(None)
    assert specs[1]["bias"] == P(None)
    for s in specs:
        # omega is the shared full-precision recombination vector and
        # alpha2 a scalar: always replicated
        assert all(a is None for a in s["omega"])
        assert s["alpha2"] == P()


# ------------------------------------------------------- sharded plans

def test_sharded_plan_single_device_bit_identical():
    pack = _rand_pack(DIMS)
    ref = serving.build_plan(pack, mode="per_layer")
    shp = serving.build_plan(pack, mode="sharded", mesh=fit_mesh())
    for b in (1, 5):
        x = jnp.asarray(np.random.default_rng(b).normal(size=(b, DIMS[0])),
                        jnp.float32)
        np.testing.assert_array_equal(np.asarray(ref.run(x)),
                                      np.asarray(shp.run(x)))
    desc = shp.describe()["sharding"]
    assert desc["n_devices"] == 1


def test_sharded_plan_resolves_blocks_before_tracing(monkeypatch):
    """The per-layer blocks are resolved eagerly, for the unsharded layer
    shapes, and reach the shard_map body as explicit values: a TPU sweep
    must time real kernels, not the tracers inside the program."""
    import jax
    from repro.kernels import ops as kops
    calls = []
    real = kops.matmul_blocks

    def spy(m, k, n, **kw):
        calls.append((m, k, n, isinstance(jnp.zeros(()), jax.core.Tracer)))
        return real(m, k, n, **kw)

    monkeypatch.setattr(kops, "matmul_blocks", spy)
    pack = _rand_pack(DIMS)
    shp = serving.build_plan(pack, mode="sharded", mesh=fit_mesh())
    shp.run(jnp.zeros((5, DIMS[0]), jnp.float32))
    assert calls and not any(traced for *_, traced in calls)
    assert {(k, n) for _, k, n, _ in calls} == {
        (k + k % 2, n) for k, n in zip(DIMS[:-1], DIMS[1:])}


def test_sharded_plan_requires_mesh():
    with pytest.raises(ValueError, match="mesh"):
        serving.build_plan(_rand_pack(DIMS), mode="sharded")


def test_sharded_plan_multidevice_bit_identical():
    """4 fake devices, (data=2, model=2): the column-split program must be
    bit-identical to the per-layer chain — fp32 and the int8 grid — with
    the odd-width layer falling back to replication."""
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro import serving
from repro.core import bitplanes as bp
from repro.launch.mesh import fit_mesh

dims = (16, 12, 7, 6)
rng = np.random.default_rng(0)
layers = []
for i, (k, n) in enumerate(zip(dims[:-1], dims[1:])):
    codes = rng.integers(0, 16, size=(k + (k % 2), n)).astype(np.uint8)
    if k % 2:
        codes[-1] = 0          # pack invariant: odd K pads a zero row
    layers.append({
        "packed": bp.pack_codes_rows(jnp.asarray(codes)),
        "omega": jnp.asarray(rng.normal(size=4) / np.sqrt(k), jnp.float32),
        "alpha1": jnp.asarray(rng.normal(size=n) * 0.5, jnp.float32),
        "bias": jnp.asarray(rng.normal(size=n) * 0.1, jnp.float32),
        "alpha2": jnp.asarray(np.float32(1.0)),
        "shape": (k, n),
        "activation": "relu" if i < len(dims) - 2 else None,
    })
pack = {"layers": layers, "act_bits": None}

mesh = fit_mesh()
assert dict(zip(mesh.axis_names, mesh.devices.shape)) == \\
    {"data": 2, "model": 2}, mesh

for extra in ({}, {"act_dtype": "int8"}):
    ref = serving.build_plan(pack, mode="per_layer", **extra)
    shp = serving.build_plan(pack, mode="sharded", mesh=mesh, **extra)
    desc = shp.describe()["sharding"]
    assert desc["n_devices"] == 4, desc
    assert 1 in desc["replicated_layers"], desc       # width 7 fallback
    for b in (1, 4, 6):
        x = jnp.asarray(np.random.default_rng(b).normal(size=(b, dims[0])),
                        jnp.float32)
        ya, yb = np.asarray(ref.run(x)), np.asarray(shp.run(x))
        assert np.array_equal(ya, yb), (extra, b, np.abs(ya - yb).max())
print("sharded-parity-ok")
""", n_devices=4)


# ----------------------------------------------------------- fit_mesh

def test_fit_mesh_shapes_and_errors():
    out = run_with_devices("""
import jax
from repro.launch.mesh import describe, fit_mesh
shapes = {n: tuple(fit_mesh(n).devices.shape) for n in (1, 2, 4, 6, 8)}
assert shapes == {1: (1, 1), 2: (2, 1), 4: (2, 2), 6: (3, 2), 8: (4, 2)}, \\
    shapes
assert tuple(fit_mesh(8, model=4).devices.shape) == (2, 4)
assert fit_mesh().devices.size == 8                 # default: all devices
assert fit_mesh(100).devices.size == 8              # capped at the host
for bad in (lambda: fit_mesh(0), lambda: fit_mesh(8, model=3)):
    try:
        bad()
    except ValueError:
        pass
    else:
        raise AssertionError("expected ValueError")
print("fit-mesh-ok")
""", n_devices=8)
    assert "fit-mesh-ok" in out


def test_fit_mesh_single_device_host():
    mesh = fit_mesh()
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == \
        {"data": 1, "model": 1}


# ------------------------------------------------------ replay streams

def test_replay_n_streams_results_identical_and_not_slower():
    plan = serving.build_plan(_rand_pack(DIMS), mode="oracle")
    rng = np.random.default_rng(7)
    xs = [jnp.asarray(rng.normal(size=(1 + i % 3, DIMS[0])), jnp.float32)
          for i in range(24)]
    arrivals = np.cumsum(rng.exponential(2e-4, size=len(xs)))
    table = {b: 1e-3 * b for b in plan.bucket_sizes}
    legs = {n: serving.replay(plan, xs, arrivals, max_delay=1e-3,
                              max_bucket=4, service_times=table, n_streams=n)
            for n in (1, 2, 3)}
    for n, rep in legs.items():
        assert rep["n_streams"] == n
        assert len(rep["stream_launches"]) == n
        for a, b in zip(legs[1]["results"], rep["results"]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert legs[2]["throughput_rps"] >= legs[1]["throughput_rps"] - 1e-9
    assert legs[3]["throughput_rps"] >= legs[2]["throughput_rps"] - 1e-9


def test_replay_n_streams_validates():
    plan = serving.build_plan(_rand_pack(DIMS), mode="oracle")
    with pytest.raises(ValueError, match="n_streams"):
        serving.replay(plan, [jnp.zeros((1, DIMS[0]))], [0.0], n_streams=0)


# ---------------------------------------------------- frontend streams

def test_frontend_streams_parity_and_stats():
    plan = serving.build_plan(_rand_pack(DIMS), mode="oracle")
    fe = serving.ServingFrontend(streams=2)
    assert fe.streams == 2
    fe.register("m", plan, max_delay=1e-3)
    rng = np.random.default_rng(1)
    xs = [rng.normal(size=(1 + i % 2, DIMS[0])).astype(np.float32)
          for i in range(24)]
    with fe:
        futs = [fe.submit("m", x) for x in xs]
        outs = [f.result(60.0) for f in futs]
    for x, out in zip(xs, outs):
        assert not isinstance(out, serving.Rejected), out
        assert out.stream in (0, 1)
        # padding parity: the request alone through the bucket that served
        # it, bit for bit (a mis-scatter shows here)
        alone = np.zeros((out.bucket, DIMS[0]), np.float32)
        alone[:len(x)] = x
        np.testing.assert_array_equal(
            out.y, np.asarray(plan.entry(out.bucket)(alone))[:len(x)])
        # and the direct run at the request's own size, to f32 rounding:
        # XLA's CPU dot rounds a row differently at another row count
        direct = np.asarray(plan.run(x))
        np.testing.assert_allclose(out.y, direct, rtol=1e-6,
                                   atol=1e-6 * np.abs(direct).max())
    st = fe.stats
    assert len(st["streams"]) == 2
    assert sum(s["launches"] for s in st["streams"]) == st["launches"]
    assert st["by_model"]["m"]["requests"] == len(xs)
    here = [str(jax.devices()[0])]
    for s in st["streams"]:
        if s["launches"]:
            assert s["batch_devices"] == s["result_devices"] == here, s


def test_frontend_streams_record_their_devices():
    """On a 4-device host each stream worker launches on its own device
    and records where its batches and results lived."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro import serving
from repro.core import bitplanes as bp

rng = np.random.default_rng(0)
k, n = 16, 8
layer = {"packed": bp.pack_codes_rows(jnp.asarray(
             rng.integers(0, 16, size=(k, n)).astype(np.uint8))),
         "omega": jnp.asarray(rng.normal(size=4), jnp.float32),
         "alpha1": jnp.ones((n,), jnp.float32),
         "bias": jnp.zeros((n,), jnp.float32),
         "alpha2": jnp.asarray(np.float32(1.0)),
         "shape": (k, n), "activation": None}
plan = serving.build_plan({"layers": [layer], "act_bits": None},
                          mode="oracle")
fe = serving.ServingFrontend(streams=4)
fe.register("m", plan, max_delay=1e-3)
with fe:
    futs = [fe.submit("m", rng.normal(size=(1, k)).astype(np.float32))
            for _ in range(64)]
    for f in futs:
        f.result(60.0)
devs = [str(d) for d in jax.devices()]
used = 0
for s in fe.stats["streams"]:
    assert s["device"] in devs, s
    if s["launches"]:
        used += 1
        assert s["batch_devices"] == s["result_devices"] == [s["device"]], s
assert used >= 1
print("stream-devices-ok", used)
""", n_devices=4)
    assert "stream-devices-ok" in out


def test_frontend_single_stream_has_no_stream_workers():
    fe = serving.ServingFrontend()
    assert fe.streams == 1
    plan = serving.build_plan(_rand_pack(DIMS), mode="oracle")
    fe.register("m", plan)
    with fe:
        out = fe.submit("m", np.zeros((1, DIMS[0]), np.float32)).result(30.0)
    assert out.stream == 0
    assert len(fe.stats["streams"]) == 1


def test_join_shortest_work_and_stream_quarantine():
    """Deterministic unit checks on the dispatch policy: argmin estimated
    work with index tie-break, and quarantine removing a stream from the
    active set while recording why."""
    fe = serving.ServingFrontend(streams=3)
    fe._stream_load[:] = [0.5, 0.1, 0.9]
    assert fe._assign_stream() == 1
    fe._stream_load[:] = [0.2, 0.2, 0.2]
    assert fe._assign_stream() == 0               # tie -> lowest index
    fe._quarantine_stream(0, RuntimeError("injected"))
    assert fe._assign_stream() == 1
    st = fe.stats["streams"][0]
    assert st["quarantined"] and "injected" in st["error"]
    assert fe._stream_load[0] == 0.0
    # idempotent: a second report must not double-account
    fe._quarantine_stream(0, RuntimeError("again"))
    assert "injected" in fe.stats["streams"][0]["error"]


# ------------------------------------------------------------ run.py

def test_bench_runner_rejects_unknown_only_key():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join((REPO, os.path.join(REPO, "src")))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "not_a_bench"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    blob = proc.stdout + proc.stderr
    assert "not_a_bench" in blob
    assert "multi_stream" in blob                 # lists the valid keys
