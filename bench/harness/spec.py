"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by name:

* ``bench/configs/<config>.json``: the model as run, its cut and the
  serve settings;
* ``bench/traffic/<mix>.json``: the mix's parameters, naming a generator
  ``kind`` whose code is ``bench/traffic/<kind>.py``;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric.

Adding a cell, a mix, a generator kind or a metric adds files and edits
none.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path (names may hold '.' and '-')."""
    spec = importlib.util.spec_from_file_location(
        "bench_dyn_" + "".join(c if c.isalnum() else "_" for c in name),
        path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_dir: str = BENCH_DIR,
              benchmark: Optional[Dict[str, Any]] = None) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics."""
    if benchmark is None:
        benchmark = load_json(os.path.join(os.path.dirname(bench_dir),
                                           "BENCHMARK.json"))
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    config = load_json(os.path.join(bench_dir, "configs",
                                    w["config"] + ".json"))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in benchmark["end_to_end"]
                    if _applies(m, name)],
        per_layer=[m for m in benchmark["per_layer"] if _applies(m, name)])


def traffic_generator(traffic: Dict[str, Any], bench_dir: str = BENCH_DIR):
    """The generator module named by a mix's ``kind``."""
    kind = traffic["kind"]
    return load_module(os.path.join(bench_dir, "traffic", kind + ".py"),
                       "traffic_" + kind)


def metric_reader(metric: str, bench_dir: str = BENCH_DIR):
    """``read(run)`` of one per-layer metric."""
    return load_module(os.path.join(bench_dir, "metrics", metric + ".py"),
                       "metric_" + metric).read
