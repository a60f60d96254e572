"""A configuration of any model family is new files only, and every
configuration binds its blocks from a file of its own, at the plan build
and at the warm-up.

Each run here goes in a child process (the CPU backend, the look for a TPU
skipped) over a temporary copy of the benchmark, to which the test adds
files and ``BENCHMARK.json`` entries and edits nothing."""
from cbtest import isolated_autotune  # noqa: F401  (autouse)
import copy
import hashlib
import importlib.util
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
# record one configuration's bindings with the copy's own recorder, as
# ``tools/record_bindings.py --config <name>`` does on the chip
RECORD = ("import json, sys; sys.path[:0] = sys.argv[1:3]; "
          "import importlib.util as u; "
          "s = u.spec_from_file_location('cb_record', "
          "sys.argv[1] + '/tools/record_bindings.py'); "
          "rec = u.module_from_spec(s); s.loader.exec_module(rec); "
          "name = sys.argv[3]; "
          "print(json.dumps(rec.record(rec.loader.config(name), "
          "rec.loader.bindings_path(name), rec.cells_of(name))))")
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


def control_model(config, seed):
    return Model(config, seed)


def inputs(model, rng, rows):
    return rng.normal(size=(rows, model.d_in)).astype(np.float32)


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

# a family shaped as a language model: token ids in, the last position's
# logits out; its configuration is cut in depth, and its control model
# keeps fewer layers than the configuration
TOY_LM_FAMILY = '''"""A toy causal LM: an embedding, single-head causal attention layers
with a residual, and a head; token ids in, the last position's logits out.
"""
import jax
import jax.numpy as jnp
import numpy as np

PLAN_KEYS = ("form", "layers")
KEYS = ("vocab_size", "d_model", "n_layers", "seq_len")
HI = jax.lax.Precision.HIGHEST


def shapes(config):
    return {k: int(config[k]) for k in KEYS}


def ops(shapes, rows):
    v, d, n, s = (shapes[k] for k in KEYS)
    return float(rows * (n * (8 * s * d * d + 4 * s * s * d) + 2 * d * v))


def bytes_moved(shapes, rows, input_bytes=4):
    v, d, n, s = (shapes[k] for k in KEYS)
    return float(rows * s * input_bytes + rows * v * 4
                 + 4 * (4 * n * d * d + 2 * v * d))


def seed_key(seed, stream):
    word = np.random.SeedSequence([seed % 2 ** 64, stream]).generate_state(1)
    return jax.random.PRNGKey(int(word[0]))


def seed_rng(seed, stream):
    return np.random.default_rng([seed % 2 ** 64, stream])


def _dot(spec, a, b, dt):
    return jnp.einsum(spec, a.astype(dt), b.astype(dt), precision=HI,
                      preferred_element_type=jnp.float32)


def _forward(emb, layers, head, ids, dt):
    h = emb[ids]
    s, d = ids.shape[1], emb.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for wq, wk, wv, wo in layers:
        q, k, v = (_dot("rsd,de->rse", h, w, dt) for w in (wq, wk, wv))
        scores = jnp.where(causal, _dot("rsd,rtd->rst", q, k, dt)
                           / np.sqrt(d), -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        h = h + _dot("rse,ed->rsd", _dot("rst,rtd->rsd", p, v, dt), wo, dt)
    return _dot("rd,dv->rv", h[:, -1], head, dt)


class Plan:
    def __init__(self, model):
        self.n = len(model.layers)
        self.run = jax.jit(lambda ids: _forward(
            model.emb, model.layers, model.head, ids, jnp.float32))

    def describe(self):
        return {"form": "jit", "layers": self.n}


class Model:
    def __init__(self, config, seed):
        v, d, n, s = (shapes(config)[k] for k in KEYS)
        key = seed_key(seed, 0)
        ke, kh = jax.random.split(jax.random.fold_in(key, 0))
        self.emb = jax.random.normal(ke, (v, d), jnp.float32)
        self.head = jax.random.normal(kh, (d, v), jnp.float32) / np.sqrt(d)
        self.layers = [tuple(jax.random.normal(k, (d, d), jnp.float32)
                             / np.sqrt(d) for k in jax.random.split(
                                 jax.random.fold_in(key, 1 + i), 4))
                       for i in range(n)]
        self.seq_len, self.vocab, self.d_out = s, v, v

    def device_arrays(self):
        return [self.emb, self.head] + [w for l in self.layers for w in l]

    def plan(self):
        return Plan(self)

    def reference(self, x):
        f = lambda a: np.asarray(a, np.float64)
        ids = np.asarray(x)
        h = f(self.emb)[ids]
        s, d = ids.shape[1], h.shape[2]
        causal = np.tril(np.ones((s, s), bool))
        for wq, wk, wv, wo in self.layers:
            q, k, v = h @ f(wq), h @ f(wk), h @ f(wv)
            scores = np.where(causal, q @ k.transpose(0, 2, 1) / np.sqrt(d),
                              -np.inf)
            p = np.exp(scores - scores.max(axis=-1, keepdims=True))
            p /= p.sum(axis=-1, keepdims=True)
            h = h + (p @ v) @ f(wo)
        return h[:, -1] @ f(self.head)

    def control(self, x, kind):
        """The forward with every product's operands in bfloat16."""
        if kind != "bfloat16":
            raise ValueError(f"unknown control {kind!r}")
        return np.asarray(_forward(self.emb, self.layers, self.head,
                                   jnp.asarray(x), jnp.bfloat16))


def control_model(config, seed):
    """One layer, a whole period of the pattern, at the published widths."""
    return Model({**config, "n_layers": 1}, seed)


def inputs(model, rng, rows):
    return rng.integers(0, model.vocab, size=(rows, model.seq_len),
                        dtype=np.int32)


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
# its traffic kind: closed back-to-back calls over token batches drawn from
# the seed
TOKENS_TRAFFIC = '''"""Closed back-to-back calls over token batches drawn from the seed,
timed and sampled as ``harness.offline`` does."""
import jax
import numpy as np

from harness.offline import Offline


class Traffic(Offline):
    def setup(self):
        run = self.run
        rng = run.rng(1)
        self.batches = [jax.device_put(run.family.inputs(run.model, rng,
                                                         self.rows))
                        for _ in range(self.pool)]
        for _ in range(2):
            np.asarray(run.plan.run(self.batches[0]))
'''
TOY_LM_CONFIG = {"name": "toy-lm", "source": "https://example.org/toy-lm",
                 "vocab_size": 512, "d_model": 32, "n_layers": 2,
                 "seq_len": 16, "act_dtype": "float32",
                 "controls": ["bfloat16"], "family": "toy_lm",
                 "reduced": ["n_layers"], "published": {"n_layers": 4},
                 "deployment": "one chip holds every layer whole"}

# the dense family with a plan that binds a block on its first call, as a
# kernel called without a block size does: at the warm-up, not the build
TOY_LAZY_FAMILY = TOY_FAMILY + '''

class LazyPlan(Plan):
    def __init__(self, w1, w2):
        super().__init__(w1, w2)
        run = self.run

        def first(x):
            from repro.kernels import autotune
            autotune.get_block_config(x.shape[0], x.shape[1], 10, fused=True,
                                      backend="interpret", extra="toy-lazy")
            self.run = run
            return run(x)
        self.run = first


Model.plan = lambda self: LazyPlan(self.w1, self.w2)
'''
TOY_LAZY_CONFIG = dict(TOY_CONFIG, name="toy-lazy", family="toy_lazy")
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
# the spec-driven cases that have to pass for a new cell and configuration
SPEC_CASES = ("test_cb_layout.py::test_cell_files_agree_with_spec",
              "test_cb_layout.py::test_config_files",
              "test_cb_reference.py::test_control_fails_the_cells_limit")


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

    def add_cell(config, family_source=None, bindings=None,
                 traffic="offline", traffic_source=None, params=None):
        b = root / "chipbench"
        cell = f"{config['name']}-{traffic}"
        if family_source is not None:
            (b / "models" / (config["family"] + ".py")).write_text(
                family_source)
        if traffic_source is not None:
            (b / "traffic" / (traffic + ".py")).write_text(traffic_source)
        (b / "configs" / (config["name"] + ".json")).write_text(
            json.dumps(config))
        if bindings is not None:
            (b / "bindings" / (config["name"] + ".json")).write_text(
                json.dumps(bindings))
        why = "a test's cell"
        (b / "workloads" / (cell + ".json")).write_text(json.dumps({
            "config": config["name"], "traffic": traffic, "chips": 1,
            "why": why, "params": params or {"rows": 40},
            "limits": {"max_rel_err": 1e-4}}))
        spec = json.loads((root / "BENCHMARK.json").read_text())
        spec["configs"].append({"name": config["name"],
                                "source": config["source"],
                                "file": f"chipbench/configs/{config['name']}"
                                        ".json",
                                "reduced": config["reduced"], "why": why})
        spec["workloads"].append({"name": cell, "config": config["name"],
                                  "traffic": traffic, "chips": 1,
                                  "why": why})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if m["name"] in REPORTS:
                m["workloads"].append(cell)
        (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=2))
        return cell

    return root, before, add_cell


def _env(tmp_path, **more):
    return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1",
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax-cache"),
                **more)


def _run(root, cell, tmp_path):
    return subprocess.run(
        [sys.executable, "-c", RUN, str(root / "chipbench"), SRC,
         "--workload", cell, "--seed", str(SEED), "--seconds", "0.3",
         "--trace", "0"],
        env=_env(tmp_path), capture_output=True, text=True, timeout=600)


def _record(root, config_name, tmp_path):
    """The copy's ``record_bindings.record`` for one configuration, with
    the warm-up of its cells, in a child process; its JSON line."""
    proc = subprocess.run(
        [sys.executable, "-c", RECORD, str(root / "chipbench"), SRC,
         config_name], env=_env(tmp_path), capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _spec_tests(root, tmp_path):
    """The benchmark's own tests that read every cell, configuration and
    metric of ``BENCHMARK.json``, run over the copy."""
    tests = root / "chipbench" / "tests"
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
         "-p", "no:xdist", str(tests / "test_cb_layout.py"),
         str(tests / "test_cb_reference.py")],
        cwd=root, env=_env(tmp_path, PYTHONPATH=SRC), capture_output=True,
        text=True, timeout=900)


def _unchanged(root, before):
    after = _digests(root / "chipbench")
    assert {k: after.get(k) for k in before} == before


def _new_cell_is_files_only(root, before, cell, config, tmp_path):
    """The cell runs correct, the spec-driven tests pass over the copy with
    its cases, and no file the copy had has changed; returns the run's
    log."""
    proc = _run(root, cell, tmp_path)
    assert proc.returncode == 0, proc.stderr[-4000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "rows_per_s"}
    assert res["checks"]["max_rel_err"]["value"] < 1e-5
    tests = _spec_tests(root, tmp_path)
    assert tests.returncode == 0, tests.stdout[-4000:]
    for case in SPEC_CASES:
        assert any(line.startswith("PASSED") and
                   f"{case}[{config['name']}" in line
                   for line in tests.stdout.splitlines()), case
    _unchanged(root, before)
    return proc.stderr


def test_new_family_is_new_files_only(bench_copy, tmp_path):
    """A toy family with its own reference and control, a config, a
    bindings file and a cell on the ``offline`` kind: a correct result
    line, the benchmark's spec-driven tests pass over the copy, toy cell
    included, and no file that the copy already had has changed."""
    root, before, add_cell = bench_copy
    old = json.loads((root / "BENCHMARK.json").read_text())
    cell = add_cell(TOY_CONFIG, TOY_FAMILY, bindings={})
    log = _new_cell_is_files_only(root, before, cell, TOY_CONFIG, tmp_path)
    assert 'plan: {"form": "dense"}' in log
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


def test_new_language_model_is_new_files_only(bench_copy, tmp_path):
    """A family that takes token ids and gives ``(rows, vocab)`` logits,
    with a float64 reference and a bfloat16 control; a configuration cut in
    depth that states its cut; a control model with fewer layers than the
    configuration; a traffic kind of its own over seeded token batches:
    all added as files and entries, the cell runs correct, the spec-driven
    tests pass over the copy with its cases, and no file changed."""
    root, before, add_cell = bench_copy
    cell = add_cell(TOY_LM_CONFIG, TOY_LM_FAMILY, bindings={},
                    traffic="tokens", traffic_source=TOKENS_TRAFFIC,
                    params={"rows": 24})
    log = _new_cell_is_files_only(root, before, cell, TOY_LM_CONFIG,
                                  tmp_path)
    assert 'plan: {"form": "jit", "layers": 2}' in log
    spec = importlib.util.spec_from_file_location(
        "cb_toy_lm", root / "chipbench" / "models" / "toy_lm.py")
    lm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lm)
    small = lm.control_model(TOY_LM_CONFIG, SEED)
    assert 1 <= len(small.layers) < TOY_LM_CONFIG["n_layers"]
    ids = lm.inputs(small, lm.seed_rng(SEED, 1), 5)
    assert ids.shape == (5, TOY_LM_CONFIG["seq_len"])
    assert ids.dtype == "int32"
    assert small.reference(ids).shape == (5, TOY_LM_CONFIG["vocab_size"])


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


# case: (config, its family's source or None for mlp4bit, its bindings
# file: None for none, "record" for what the copy's recorder records)
BINDINGS_CASES = {
    "missing": (TINY_INT8, None, None),
    "added": (TINY_INT8, None, {}),
    "recorded": (TINY_INT8, None, "record"),
    "warm-up added": (TOY_LAZY_CONFIG, TOY_LAZY_FAMILY, {}),
    "warm-up recorded": (TOY_LAZY_CONFIG, TOY_LAZY_FAMILY, "record"),
}


@pytest.mark.parametrize("case", list(BINDINGS_CASES))
def test_bindings_check(case, bench_copy, tmp_path):
    """A configuration without a bindings file, or whose plan build or
    warm-up binds a block the file lacks, exits 1, names the file and the
    command that records it, and prints no result; one recorded by
    ``tools/record_bindings.py`` (the plan build and every cell's warm-up)
    runs, and recording it leaves every other file byte for byte as it
    was."""
    root, before, add_cell = bench_copy
    config, family, bindings = BINDINGS_CASES[case]
    cell = add_cell(config, family,
                    bindings=None if bindings == "record" else bindings)
    target = root / "chipbench" / "bindings" / f"{config['name']}.json"
    if bindings == "record":
        line = _record(root, config["name"], tmp_path)
        assert line["failures"] == []
        recorded = json.loads(target.read_text())
        if family is None:
            assert line["interpret"] and recorded
        else:   # the warm-up's binding, which the plan build never makes
            assert [k for k in recorded if "toy-lazy" in k] == \
                list(recorded)
            assert len(recorded) == 1
    proc = _run(root, cell, tmp_path)
    rel = f"chipbench/bindings/{config['name']}.json"
    if bindings == "record":
        assert proc.returncode == 0, proc.stderr[-4000:]
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        assert res["correct"] is True, res["checks"]
    else:
        assert proc.returncode == 1, proc.stderr[-4000:]
        assert proc.stdout.strip() == ""
        assert rel in proc.stderr
        assert f"record_bindings.py --config {config['name']}" in \
            proc.stderr
        if case == "added":
            assert "the plan build swept block bindings" in proc.stderr
        if case == "warm-up added":
            assert "the warm-up swept block bindings" in proc.stderr
    _unchanged(root, before)
