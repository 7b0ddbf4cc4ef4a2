"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by name:

* ``bench/configs/<config>.json``: the model as run, its cut and the
  serve settings, and its model ``family``;
* ``bench/families/<family>.py``: the harness side of that family;
* ``bench/reference/<family>.py``: the family's plain reference;
* ``bench/traffic/<mix>.json``: the mix's parameters, naming a generator
  ``kind`` whose code is ``bench/traffic/<kind>.py``;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric.

Adding a cell, a mix, a generator kind, a metric or a model family adds
files and edits none.

The harness reads only ``family``, ``program``, ``serve``, ``mesh`` and
``limits`` of a configuration file; everything else in it is the
family's to read.  A family brings two files, found by its name:

``bench/families/<family>.py`` defines

* ``dims(cfg)``: an object with at least ``vocab`` and ``layers``, and
  whatever the family's counts need; traffic and metrics read it;
* ``program_config(name, cfg)``: the program's model (its registry
  entry at the file's depth), raising ``ValueError`` where the program's
  model is not the file's;
* ``layout(cfg)``: the weight tree as the program's parameter tree
  holds it, each leaf ``(shape, dtype, kind, std)`` with ``kind``
  ``"normal"`` or ``"norm"`` (``bench/harness/weights.py`` draws it);
* ``request_flops(dims, prompt_len, new_tokens)``: the whole model's
  operations for one request (``mfu``);
* ``paged_kernel_work(dims, prompt_len, new_tokens, model_shards)``:
  ``(bytes, flops)`` of one chip's paged decode kernel over one
  request's decode steps, for one of ``model_shards`` tensor-parallel
  shards (``paged_attn_roofline``).

``bench/reference/<family>.py`` imports nothing of the program and
defines

* ``Spec.from_config(cfg)``: the hashable settings of the forward pass;
* ``logits_at(weights, spec, tokens, rows, precision="float32")``:
  float32 logits ``(len(rows), vocab)`` at positions ``rows`` of one
  sequence, from the drawn weight tree; ``precision="fp8"`` is the
  control;
* ``gaps(ref_logits, tokens)``: per row, how far the logit of
  ``tokens[i]`` lies below the row's best.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path (names may hold '.' and '-').  The module
    is entered in ``sys.modules`` while it runs, as an import would,
    since dataclasses look their module up there."""
    modname = "bench_dyn_" + "".join(c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
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
    bench_dir: str = BENCH_DIR  # where the configuration and its family lie


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
        per_layer=[m for m in benchmark["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)


def traffic_generator(traffic: Dict[str, Any], bench_dir: str = BENCH_DIR):
    """The generator module named by a mix's ``kind``."""
    kind = traffic["kind"]
    return load_module(os.path.join(bench_dir, "traffic", kind + ".py"),
                       "traffic_" + kind)


def metric_reader(metric: str, bench_dir: str = BENCH_DIR):
    """``read(run)`` of one per-layer metric."""
    return load_module(os.path.join(bench_dir, "metrics", metric + ".py"),
                       "metric_" + metric).read


def _family_name(cfg: Dict[str, Any]) -> str:
    if "family" not in cfg:
        raise KeyError("the configuration names no model 'family' "
                       "(bench/families/<family>.py)")
    return cfg["family"]


@functools.lru_cache(maxsize=None)
def _load_once(path: str, name: str):
    return load_module(path, name)


def family(cfg: Dict[str, Any], bench_dir: str = BENCH_DIR):
    """The harness side of the configuration's model family."""
    name = _family_name(cfg)
    return _load_once(os.path.join(bench_dir, "families", name + ".py"),
                      "family_" + name)


def reference(cfg: Dict[str, Any], bench_dir: str = BENCH_DIR):
    """The plain reference of the configuration's model family."""
    name = _family_name(cfg)
    return _load_once(os.path.join(bench_dir, "reference", name + ".py"),
                      "reference_" + name)
