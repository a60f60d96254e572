"""Find the benchmark's parts by name.

Everything that belongs to one configuration, one cell, one traffic kind or
one per-layer metric lives in a file of its own, named after it:

    configs/<config>.json      the model: widths, dtypes, model family
    workloads/<cell>.json      the cell: config, traffic kind and its data
    traffic/<kind>.py          the generator for one kind of traffic
    metrics/<metric>.py        the reader of one per-layer metric
    models/<family>.py         weights, serving plan and plain reference

and ``BENCHMARK.json`` at the root of the checkout says which metrics each
cell reports.  Adding a cell or a metric adds files; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from types import ModuleType
from typing import List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _path(kind: str, name: str, ext: str) -> str:
    if not _NAME.match(name) or ".." in name:
        raise ValueError(f"bad {kind} name {name!r}")
    path = os.path.join(BENCH, kind, name + ext)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    return path


def read_json(kind: str, name: str) -> dict:
    with open(_path(kind, name, ".json")) as f:
        return json.load(f)


def module(kind: str, name: str) -> ModuleType:
    """Import ``<kind>/<name>.py`` (names may hold dots and dashes)."""
    key = f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, _path(kind, name,
                                                             ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def workload(name: str) -> dict:
    w = read_json("workloads", name)
    w.setdefault("name", name)
    return w


def config(name: str) -> dict:
    c = read_json("configs", name)
    c.setdefault("name", name)
    return c


def traffic(kind: str) -> ModuleType:
    return module("traffic", kind)


def family(name: str) -> ModuleType:
    return module("models", name)


def metric_reader(name: str) -> ModuleType:
    return module("metrics", name)


def spec(path: Optional[str] = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str, reported: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # no list: every cell that reports the end-to-end metric it moves
    return metric.get("moves") in reported


def metrics_for(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The end-to-end (``trace`` False) or per-layer (True) metric entries
    that ``cell`` reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    names = [m["name"] for m in e2e]
    return [m for m in bench["per_layer"] if _applies(m, cell, names)]


def cell_entry(bench: dict, cell: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == cell:
            return w
    raise KeyError(f"BENCHMARK.json has no cell {cell!r}")
