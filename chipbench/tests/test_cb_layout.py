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


@pytest.mark.parametrize("config", SPEC["configs"],
                         ids=lambda c: c["name"])
def test_config_files(config):
    cfg = loader.config(config["name"])
    assert os.path.join(loader.ROOT, config["file"]) == os.path.join(
        loader.BENCH, "configs", config["name"] + ".json")
    assert cfg["source"] == config["source"]
    assert cfg["reduced"] == config["reduced"] == []
    assert any(w["config"] == config["name"] for w in SPEC["workloads"])


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
