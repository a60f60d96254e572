"""A configuration of any model family is new files only, and every
configuration binds its blocks from a file of its own.

Each run here goes in a child process (the CPU backend, the look for a TPU
skipped) over a temporary copy of the benchmark, to which the test adds
files and ``BENCHMARK.json`` entries and edits nothing."""
from cbtest import isolated_autotune  # noqa: F401  (autouse)
import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import loader

SRC = os.path.join(loader.ROOT, "src")
RUN = ("import sys; sys.path[:0] = sys.argv[1:3]; "
       "from harness.bench import execute; "
       "sys.exit(execute(sys.argv[3:], require_chip=False))")
SEED = 2 ** 31 + 3

# a family that shares no code with mlp4bit: a dense float32 two-layer MLP
# served by one jitted forward, with its own reference in float64 numpy
TOY_FAMILY = '''"""A dense two-layer float32 MLP, served by one jitted forward."""
import jax
import jax.numpy as jnp
import numpy as np

PLAN_KEYS = ("form",)


def shapes(config):
    k, h, n = config["widths"]
    return [(k, h), (h, n)]


def ops(shapes, rows):
    return 2.0 * rows * sum(k * n for k, n in shapes)


def bytes_moved(shapes, rows, input_bytes=4):
    return float(rows * shapes[0][0] * input_bytes + rows * shapes[-1][1] * 4
                 + sum(4 * k * n for k, n in shapes))


def seed_key(seed, stream):
    word = np.random.SeedSequence([seed % 2 ** 64, stream]).generate_state(1)
    return jax.random.PRNGKey(int(word[0]))


def seed_rng(seed, stream):
    return np.random.default_rng([seed % 2 ** 64, stream])


class Plan:
    def __init__(self, w1, w2):
        hi = jax.lax.Precision.HIGHEST
        self.run = jax.jit(lambda x: jnp.dot(
            jnp.maximum(jnp.dot(x, w1, precision=hi), 0.0), w2,
            precision=hi))

    def describe(self):
        return {"form": "dense"}


class Model:
    def __init__(self, config, seed):
        (k, h), (_, n) = shapes(config)
        k1, k2 = jax.random.split(seed_key(seed, 0))
        self.w1 = jax.random.normal(k1, (k, h), jnp.float32) / np.sqrt(k)
        self.w2 = jax.random.normal(k2, (h, n), jnp.float32) / np.sqrt(h)
        self.d_in, self.d_out = k, n

    def device_arrays(self):
        return [self.w1, self.w2]

    def plan(self):
        return Plan(self.w1, self.w2)

    def reference(self, x):
        w1 = np.asarray(self.w1, np.float64)
        w2 = np.asarray(self.w2, np.float64)
        return np.maximum(np.asarray(x, np.float64) @ w1, 0.0) @ w2

    def control(self, x, kind):
        """The forward with its products in one bfloat16 pass."""
        if kind != "default":
            raise ValueError(f"unknown control {kind!r}")
        bf, f32 = jnp.bfloat16, jnp.float32
        h = jnp.maximum(jnp.dot(jnp.asarray(x, bf), self.w1.astype(bf),
                                preferred_element_type=f32), 0.0)
        return np.asarray(jnp.dot(h.astype(bf), self.w2.astype(bf),
                                  preferred_element_type=f32))


def _gaps(served, ref):
    served, ref = np.asarray(served, np.float64), np.asarray(ref)
    if served.shape != ref.shape or not np.all(np.isfinite(served)):
        return None
    return np.abs(served - ref) / max(np.max(np.abs(ref)), 1e-30)


def max_rel_err(served, ref):
    g = _gaps(served, ref)
    return float("inf") if g is None else float(np.max(g))


def miss_count(served, ref):
    g = _gaps(served, ref)
    return float(len(ref)) if g is None else float(
        np.sum(np.max(g, axis=1) > 1e-4))
'''
TOY_CONFIG = {"name": "toy-dense2", "source": "https://example.org/toy",
              "widths": [48, 32, 10], "act_dtype": "float32",
              "controls": ["default"], "family": "toy_dense2",
              "reduced": []}
TINY_INT8 = {"name": "tiny-int8", "source": "https://example.org/tiny",
             "d_in": 64, "features": [128, 64, 12], "act_dtype": "int8",
             "matmul_precision": "highest", "controls": ["int4"],
             "max_bucket": 8, "family": "mlp4bit", "reduced": []}
# the metrics a test's cell reports besides setup_s
REPORTS = ("rows_per_s", "mfu")
# the configurations whose bindings were one file, and that file, as
# sha256 of its entries in canonical JSON
SPLIT = ("mlp-gsc-int8", "lenet-300-100-fp32")
OLD_BINDINGS = \
    "3e2abff8b3d8614f2c045071dfc9eae0d5aac4717a4e54d0592002e08d774ccb"


def _digests(root):
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of the benchmark and ``BENCHMARK.json``, and a function that
    adds one cell: its files and its entries."""
    root = tmp_path / "checkout"
    shutil.copytree(loader.BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copyfile(os.path.join(loader.ROOT, "BENCHMARK.json"),
                    root / "BENCHMARK.json")
    before = _digests(root / "chipbench")

    def add_cell(config, family_source=None, bindings=None):
        b = root / "chipbench"
        cell = config["name"] + "-offline"
        if family_source is not None:
            (b / "models" / (config["family"] + ".py")).write_text(
                family_source)
        (b / "configs" / (config["name"] + ".json")).write_text(
            json.dumps(config))
        if bindings is not None:
            (b / "bindings" / (config["name"] + ".json")).write_text(
                json.dumps(bindings))
        why = "a test's cell"
        (b / "workloads" / (cell + ".json")).write_text(json.dumps({
            "config": config["name"], "traffic": "offline", "chips": 1,
            "why": why, "params": {"rows": 40},
            "limits": {"max_rel_err": 1e-4}}))
        spec = json.loads((root / "BENCHMARK.json").read_text())
        spec["configs"].append({"name": config["name"],
                                "source": config["source"],
                                "file": f"chipbench/configs/{config['name']}"
                                        ".json",
                                "reduced": [], "why": why})
        spec["workloads"].append({"name": cell, "config": config["name"],
                                  "traffic": "offline", "chips": 1,
                                  "why": why})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if m["name"] in REPORTS:
                m["workloads"].append(cell)
        (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=2))
        return cell

    return root, before, add_cell


def _run(root, cell, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax-cache"))
    return subprocess.run(
        [sys.executable, "-c", RUN, str(root / "chipbench"), SRC,
         "--workload", cell, "--seed", str(SEED), "--seconds", "0.3",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=600)


def _spec_tests(root, tmp_path):
    """The benchmark's own tests that read every cell, configuration and
    metric of ``BENCHMARK.json``, run over the copy."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1",
               PYTHONPATH=SRC,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax-cache"))
    tests = root / "chipbench" / "tests"
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
         "-p", "no:xdist", str(tests / "test_cb_layout.py"),
         str(tests / "test_cb_reference.py")],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)


def _unchanged(root, before):
    after = _digests(root / "chipbench")
    assert {k: after.get(k) for k in before} == before


def test_new_family_is_new_files_only(bench_copy, tmp_path):
    """A toy family with its own reference and control, a config, a
    bindings file and a cell on the ``offline`` kind: a correct result
    line, the benchmark's spec-driven tests pass over the copy, toy cell
    included, and no file that the copy already had has changed."""
    root, before, add_cell = bench_copy
    old = json.loads((root / "BENCHMARK.json").read_text())
    cell = add_cell(TOY_CONFIG, TOY_FAMILY, bindings={})
    proc = _run(root, cell, tmp_path)
    assert proc.returncode == 0, proc.stderr[-4000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "rows_per_s"}
    assert res["checks"]["max_rel_err"]["value"] < 1e-5
    assert 'plan: {"form": "dense"}' in proc.stderr
    tests = _spec_tests(root, tmp_path)
    assert tests.returncode == 0, tests.stdout[-4000:]
    for case in ("test_cb_layout.py::test_cell_files_agree_with_spec",
                 "test_cb_layout.py::test_config_files",
                 "test_cb_reference.py::test_control_fails_the_cells_limit"):
        assert any(line.startswith("PASSED") and
                   f"{case}[{TOY_CONFIG['name']}" in line
                   for line in tests.stdout.splitlines()), case
    _unchanged(root, before)
    # BENCHMARK.json: entries added, and the new cell on the lists of the
    # metrics it reports
    new = json.loads((root / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads"):
        assert new[key][:len(old[key])] == old[key]
    for key in ("end_to_end", "per_layer"):
        listed = copy.deepcopy(old[key])
        for m in listed:
            if m["name"] in REPORTS:
                m["workloads"].append(cell)
        assert new[key] == listed


def test_split_bindings_are_the_old_file():
    """One file per configuration, each holding only its own stack's
    entries; together they are the one file every run used to copy."""
    merged = {}
    for name in SPLIT:
        cfg = loader.config(name)
        with open(loader.bindings_path(name)) as f:
            mine = json.load(f)
        stack = "stack" + "x".join(str(n) for n in cfg["features"])
        assert mine and all(k.split("|")[7] == stack for k in mine)
        assert not set(mine) & set(merged)
        merged.update(mine)
    assert len(merged) == 18
    assert hashlib.sha256(json.dumps(merged, sort_keys=True).encode()
                          ).hexdigest() == OLD_BINDINGS


@pytest.mark.parametrize("case", ["missing", "added", "recorded"])
def test_bindings_check(case, bench_copy, tmp_path, monkeypatch):
    """A configuration without a bindings file, or whose plan build adds a
    binding to it, exits 1 and prints no result; one recorded by
    ``tools/record_bindings.py`` runs, and recording it leaves every other
    configuration's file byte for byte as it was."""
    root, before, add_cell = bench_copy
    bindings = {"missing": None, "added": {}, "recorded": None}[case]
    cell = add_cell(TINY_INT8, bindings=bindings)
    if case == "recorded":
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "cb_record_bindings",
            os.path.join(loader.BENCH, "tools", "record_bindings.py"))
        rec = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(rec)
        from repro.kernels import autotune
        monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "other.json"))
        target = root / "chipbench" / "bindings" / "tiny-int8.json"
        line = rec.record(dict(TINY_INT8), str(target))
        assert line["failures"] == [] and line["interpret"]
        assert json.loads(target.read_text())
    proc = _run(root, cell, tmp_path)
    rel = "chipbench/bindings/tiny-int8.json"
    if case == "recorded":
        assert proc.returncode == 0, proc.stderr[-4000:]
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        assert res["correct"] is True, res["checks"]
    else:
        assert proc.returncode == 1, proc.stderr[-4000:]
        assert proc.stdout.strip() == ""
        assert rel in proc.stderr
        assert "record_bindings.py --config tiny-int8" in proc.stderr
        if case == "added":
            assert "swept block bindings" in proc.stderr
    _unchanged(root, before)
