"""The plain reference against the program at a tiny size (interpret mode
on the CPU), the control against each cell's limit at the configs' own
widths, and the arrival schedule."""
from cbtest import isolated_autotune  # noqa: F401  (autouse)
import numpy as np
import pytest

from harness import bench, loader, openloop

mlp = loader.family("mlp4bit")
TINY = {
    "float32": dict(d_in=80, features=[64, 10], act_dtype="float32",
                    matmul_precision="highest", controls=["high"],
                    max_bucket=8, family="mlp4bit"),
    "int8": dict(d_in=64, features=[128, 64, 12], act_dtype="int8",
                 matmul_precision="highest",
                 controls=["int4", "high", "default"], max_bucket=8,
                 family="mlp4bit"),
}


@pytest.mark.parametrize("act", ["float32", "int8"])
def test_reference_matches_plan_run(act):
    import jax.numpy as jnp
    model = mlp.Model(TINY[act], 2 ** 31 + 11)
    plan = model.plan()
    assert plan.describe()["interpret"]
    x = np.random.default_rng(0).normal(size=(40, model.d_in)).astype(
        np.float32)
    served = np.asarray(plan.run(jnp.asarray(x)))
    assert mlp.max_rel_err(served, model.reference(x, block_rows=16)) < 1e-6


def test_model_is_made_from_the_seed():
    a, b = mlp.Model(TINY["int8"], 7), mlp.Model(TINY["int8"], 7)
    c = mlp.Model(TINY["int8"], 8)
    assert np.array_equal(a.layers[0]["packed"], b.layers[0]["packed"])
    assert a.act_scales == b.act_scales
    assert not np.array_equal(a.layers[0]["packed"], c.layers[0]["packed"])
    assert a.layers[0]["packed"].dtype == np.uint8


@pytest.mark.parametrize("cell", [w["name"] for w in loader.spec()[
    "workloads"]])
def test_control_fails_the_cells_limit(cell):
    """Each control of the configuration (the reference one step below a
    precision it states: int4 activations, products in three bf16 passes
    or one), put in the program's place, misses one of the cell's limits
    at the configuration's own widths."""
    wl = loader.workload(cell)
    cfg = loader.config(wl["config"])
    run = bench.Run(bench.parse(["--workload", cell, "--seed", "3",
                                 "--seconds", "1"]), wl, cfg, 0.0)
    run.family = mlp
    run.model = mlp.Model(cfg, 3)
    x = np.random.default_rng(1).normal(size=(256, run.model.d_in)).astype(
        np.float32)
    pairs = [(x, run.model.reference(x))]
    assert all(v == 0 for v in bench.compare(run, pairs).values())
    assert cfg["controls"]
    for control in cfg["controls"]:
        found = bench.compare(run, pairs, control=control)
        assert any(found[k] > lim for k, lim in wl["limits"].items()), \
            (control, found)


def test_miss_count_counts_rows():
    ref = np.array([[1.0, -2.0], [0.5, 0.25], [3.0, 4.0]])
    got = ref.copy()
    assert mlp.miss_count(got, ref) == 0
    got[1, 1] += 2e-4 * 4.0
    got[2, 0] += 1e-5 * 4.0
    assert mlp.miss_count(got, ref) == 1
    assert mlp.miss_count(got[:, :1], ref) == 3


def test_max_rel_err_refuses_wrong_shapes_and_nan():
    ref = np.ones((4, 3))
    assert mlp.max_rel_err(np.ones((4, 2)), ref) == float("inf")
    bad = ref.copy()
    bad[0, 0] = np.nan
    assert mlp.max_rel_err(bad, ref) == float("inf")


@pytest.mark.parametrize("arrivals", [
    {"process": "poisson"},
    {"process": "onoff", "phase_requests": 16, "on_factor": 10.0,
     "off_factor": 0.1}])
def test_every_seed_offers_the_same_work(arrivals):
    def gaps(seed):
        t = openloop.offsets(2000, 1000.0, 2.0, arrivals,
                             np.random.default_rng(seed))
        return np.diff(np.concatenate([t, [2.0]]))
    a, b = gaps(1), gaps(2)
    assert a.sum() == pytest.approx(2.0) and b.sum() == pytest.approx(2.0)
    assert np.allclose(np.sort(a), np.sort(b))
    assert not np.allclose(a, b)
    assert np.all(a > 0)
