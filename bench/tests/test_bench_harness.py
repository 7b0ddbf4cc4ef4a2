"""The harness finds every file by name, and BENCHMARK.json holds to the
shape the benchmark's contract gives it."""
import json
import os
import re
import time

import pytest

import tiny
from bench.harness import runner, spec

BENCH = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_a_new_config_mix_kind_and_metric_need_only_new_files(tmp_path):
    """Everything a later cell brings is a file under a new name, a model
    family too: the test family ``moe_gqa`` (a family module and a
    reference under ``data/``) runs a whole cell through the harness and
    comes out correct."""
    d = tiny.write_bench_dir(tmp_path, {"dummy-config": tiny.config()},
                             {"dummy-mix": dict(tiny.TRAFFIC,
                                                kind="dummy_kind")})
    with open(os.path.join(d, "traffic", "dummy_kind.py"), "w") as f:
        f.write("def make(params, vocab, seed):\n"
                "    return ('made', params['batch'], vocab, seed)\n")
    os.makedirs(os.path.join(d, "metrics"))
    with open(os.path.join(d, "metrics", "dummy_metric.py"), "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    bench = tiny.benchmark([("dummy.cell", "dummy-config", "dummy-mix")])
    bench["per_layer"].append({"name": "dummy_metric", "unit": "%",
                               "workloads": ["dummy.cell"]})
    cell = spec.load_cell("dummy.cell", d, bench)
    assert cell.config == tiny.config()
    assert cell.traffic["kind"] == "dummy_kind"
    assert spec.traffic_generator(cell.traffic, d).make(
        cell.traffic, 7, 1) == ("made", 6, 7, 1)
    assert "dummy_metric" in [m["name"] for m in cell.per_layer]
    assert spec.metric_reader("dummy_metric", d)(None) == 42.0

    moe = str(tmp_path / "moe")
    tiny.write_bench_dir(moe, {"moe-config": tiny.moe_config()},
                         {"mix": tiny.TRAFFIC}, families=("moe_gqa",),
                         family_dir=tiny.DATA)
    cell = spec.load_cell("moe.cell", moe,
                          tiny.benchmark([("moe.cell", "moe-config", "mix")]))
    assert spec.family(cell.config, moe).__file__.startswith(moe)
    out = runner.run_cell(cell, 2 ** 31 + 13, 0.0, False, time.time(),
                          compile_cache=False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["compared"]["widest_logit_gap"]["value"] <= 1e-3


def test_metrics_apply_only_to_their_cells():
    bench = tiny.benchmark([("a", "x", "y"), ("b", "x", "y")])
    bench["per_layer"] = [{"name": "m1", "workloads": ["a"]},
                          {"name": "m2"}]
    d = tiny.write_bench_dir(
        __import__("tempfile").mkdtemp(), {"x": {}}, {"y": {}})
    assert [m["name"] for m in spec.load_cell("b", d, bench).per_layer] \
        == ["m2"]
    with pytest.raises(KeyError):
        spec.load_cell("c", d, bench)


def test_every_cell_finds_its_files():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["serve"]["batch_slots"] >= 1
        spec.traffic_generator(cell.traffic)
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        f = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert f["source"] == c["source"]
        assert sorted(f["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            # never a width: sizes, heads, experts per token
            assert not re.search(r"_dim$|_rank$|_size$|_heads$|per_tok",
                                 k), k
        names.add(c["name"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    four = 0
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        four += w["chips"] == 4
        reported = [m for m in BENCH["per_layer"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert reported, w["name"]
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "metrics",
                                           m["name"] + ".py"))
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("name,params", [
    ("qwen2.5-32b-8of64", 5.458e9), ("qwen2.5-32b-32of64-tp4", 17.16e9)])
def test_config_files_are_the_programs_models(name, params):
    """Each file builds the registry's model at its own depth, and the
    benchmark's weight layout is the program's parameter tree."""
    import jax

    from bench.harness import weights
    from repro.models import Model

    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      name + ".json"))
    family = spec.family(cfg)
    assert family.__file__ == os.path.join(spec.BENCH_DIR, "families",
                                           "dense_gqa.py")
    mcfg = family.program_config(name, cfg)
    assert mcfg.n_layers == cfg["num_hidden_layers"]
    have = weights.shapes(family.layout(cfg))
    want = Model(mcfg).abstract_params()
    assert jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), have) == \
        jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), want)
    n = sum(s.size for s in jax.tree_util.tree_leaves(have))
    assert n == pytest.approx(params, rel=2e-3)
    for key in cfg["reduced"]:
        assert cfg["published"][key] != cfg[key]


def test_a_file_that_is_not_the_program_is_refused():
    cfg = dict(spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "qwen2.5-32b-8of64.json")),
        intermediate_size=1000)
    with pytest.raises(ValueError):
        spec.family(cfg).program_config("x", cfg)
