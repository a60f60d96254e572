"""The benchmark finds every part by name, and BENCHMARK.json agrees with
the files."""
from cbtest import isolated_autotune  # noqa: F401  (autouse)
import os
import re

import pytest

from harness import bench, loader

SPEC = loader.spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_agree_with_spec(cell):
    entry = loader.cell_entry(SPEC, cell)
    wl = loader.workload(cell)
    for key in ("config", "traffic", "chips", "why"):
        assert wl[key] == entry[key], key
    cfg = loader.config(wl["config"])
    assert hasattr(loader.traffic(wl["traffic"]), "Traffic")
    assert hasattr(loader.family(cfg["family"]), "Model")
    assert "max_rel_err" in wl["limits"]
    assert set(wl["limits"]) <= set(bench.NUMBERS)
    e2e = [m["name"] for m in loader.metrics_for(SPEC, cell, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert loader.metrics_for(SPEC, cell, True)


# a key that names a width, which no cut may change: a hidden,
# intermediate, latent, state, projection or head size, a ``_dim`` or a
# ``_rank``, an expansion factor, the experts per token, an MLP's widths.
# Depth (``num_hidden_layers``) and the vocabulary may be cut.
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj\w*|head)_size$|"
                   r"_dim$|_rank$|^d_|^expand$|expansion|experts_per_tok|"
                   r"^features$|^widths$")


def check_cut(cfg, entry):
    """A configuration's ``reduced`` is ``BENCHMARK.json``'s; where it is
    not empty, each key it lists is a scale and not a width, the file's
    ``published`` object gives that key's published value, which the file
    changes, and ``deployment`` says how the chips share a layer."""
    assert cfg["reduced"] == entry["reduced"]
    if not cfg["reduced"]:
        return
    assert len(set(cfg["reduced"])) == len(cfg["reduced"])
    published = cfg["published"]
    for key in cfg["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
        assert key in published, key
        assert cfg[key] != published[key], key
    assert isinstance(cfg["deployment"], str) and cfg["deployment"].strip()


@pytest.mark.parametrize("config", SPEC["configs"],
                         ids=lambda c: c["name"])
def test_config_files(config):
    cfg = loader.config(config["name"])
    assert os.path.join(loader.ROOT, config["file"]) == os.path.join(
        loader.BENCH, "configs", config["name"] + ".json")
    assert cfg["source"] == config["source"]
    check_cut(cfg, config)
    assert any(w["config"] == config["name"] for w in SPEC["workloads"])


CUT = {"num_hidden_layers": 2, "vocab_size": 8192, "d_model": 64,
       "reduced": ["num_hidden_layers", "vocab_size"],
       "published": {"num_hidden_layers": 24, "vocab_size": 32000},
       "deployment": "one chip holds every layer whole"}


@pytest.mark.parametrize("fault", [
    None, "no published", "no deployment", "empty deployment",
    "key not published", "value as published", "a width cut",
    "not the spec's"])
def test_a_cut_config_states_its_cut(fault):
    """A cut configuration without ``published`` or ``deployment``, with a
    reduced key missing from ``published`` or left at its published value,
    with a width among its cuts, or with a ``reduced`` other than
    ``BENCHMARK.json``'s, fails the configuration check."""
    cfg = {**CUT, "published": dict(CUT["published"])}
    entry = {"reduced": list(CUT["reduced"])}
    if fault == "no published":
        del cfg["published"]
    elif fault == "no deployment":
        del cfg["deployment"]
    elif fault == "empty deployment":
        cfg["deployment"] = " "
    elif fault == "key not published":
        del cfg["published"]["vocab_size"]
    elif fault == "value as published":
        cfg["num_hidden_layers"] = 24
    elif fault == "a width cut":
        cfg["reduced"] = entry["reduced"] = CUT["reduced"] + ["d_model"]
        cfg["published"]["d_model"] = 2560
    elif fault == "not the spec's":
        entry["reduced"] = []
    if fault is None:
        check_cut(cfg, entry)
    else:
        with pytest.raises((AssertionError, KeyError)):
            check_cut(cfg, entry)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    assert NAME.match(metric["name"])
    assert callable(loader.metric_reader(metric["name"]).read)
    for cell in metric.get("workloads", []):
        loader.cell_entry(SPEC, cell)


def test_per_layer_metrics_move_a_reported_metric():
    for m in SPEC["per_layer"]:
        for cell in m["workloads"]:
            e2e = [e["name"] for e in loader.metrics_for(SPEC, cell, False)]
            assert m["moves"] in e2e, (m["name"], cell)


@pytest.mark.parametrize("bad", ["../run", "a/b", "", " x", "x" * 65])
def test_bad_names_are_refused(bad):
    with pytest.raises(ValueError):
        loader.workload(bad)


def test_unknown_names_are_errors():
    with pytest.raises(FileNotFoundError):
        loader.workload("no-such-cell")
    with pytest.raises(FileNotFoundError):
        loader.metric_reader("no_such_metric")
