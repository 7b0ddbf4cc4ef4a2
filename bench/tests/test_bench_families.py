"""Model families as files: the dense GQA family lays out and draws the
weights exactly as the harness did before families were files of their
own, a configuration must name its family, and no file of the harness or
of the metrics names a family or reads a model key of a configuration."""
import ast
import glob
import os

import numpy as np
import pytest

import tiny
from bench.families import dense_gqa
from bench.harness import spec, weights

# the weight leaves of a Qwen2.5-32B configuration as the harness laid them
# out before this family had a file of its own, in draw order (the drawer
# folds the key by leaf index): path, shape ("L" the layers), dtype, kind, std
QWEN_LEAVES = [
    ("blocks/0_attn/attn/bk", ("L", 8, 128), "bfloat16", "normal", 0.1),
    ("blocks/0_attn/attn/bq", ("L", 40, 128), "bfloat16", "normal", 0.1),
    ("blocks/0_attn/attn/bv", ("L", 8, 128), "bfloat16", "normal", 0.1),
    ("blocks/0_attn/attn/wk", ("L", 5120, 8, 128), "bfloat16", "normal",
     0.013975424859373685),
    ("blocks/0_attn/attn/wo", ("L", 40, 128, 5120), "bfloat16", "normal",
     0.013975424859373685),
    ("blocks/0_attn/attn/wq", ("L", 5120, 40, 128), "bfloat16", "normal",
     0.013975424859373685),
    ("blocks/0_attn/attn/wv", ("L", 5120, 8, 128), "bfloat16", "normal",
     0.013975424859373685),
    ("blocks/0_attn/ln1", ("L", 5120), "float32", "norm", 0.05),
    ("blocks/0_attn/ln2", ("L", 5120), "float32", "norm", 0.05),
    ("blocks/0_attn/mlp/wg", ("L", 5120, 27648), "bfloat16", "normal",
     0.013975424859373685),
    ("blocks/0_attn/mlp/wi", ("L", 5120, 27648), "bfloat16", "normal",
     0.013975424859373685),
    ("blocks/0_attn/mlp/wo", ("L", 27648, 5120), "bfloat16", "normal",
     0.006014065304058602),
    ("embed", (152064, 5120), "bfloat16", "normal", 0.013975424859373685),
    ("final_norm", (5120,), "float32", "norm", 0.05),
    ("lm_head", (5120, 152064), "bfloat16", "normal", 0.013975424859373685),
]

# seed 2**31 + 5 at the tiny widths, drawn by the harness before this
# family had a file of its own: each leaf's first value and its sum
TINY_DRAWS = {
    True: [
        ("blocks/0_attn/attn/bk", 0.01591343991458416, -0.46098293468821794),
        ("blocks/0_attn/attn/bq", 0.000978935626335442, -0.9576281735207886),
        ("blocks/0_attn/attn/bv", 0.20396941900253296, 0.6741011426784098),
        ("blocks/0_attn/attn/wk", -0.09005547314882278, 8.192175519969169),
        ("blocks/0_attn/attn/wo", 0.02320123091340065, -7.034808761388376),
        ("blocks/0_attn/attn/wq", 0.14751824736595154, 18.426409053172392),
        ("blocks/0_attn/attn/wv", -0.009904091246426105, -0.7792793416883796),
        ("blocks/0_attn/ln1", 1.0603325366973877, 127.60874330997467),
        ("blocks/0_attn/ln2", 0.9576106071472168, 128.54775947332382),
        ("blocks/0_attn/mlp/wg", 0.20916323363780975, 1.5502272955084209),
        ("blocks/0_attn/mlp/wi", -0.03956101834774017, -5.884260476490624),
        ("blocks/0_attn/mlp/wo", -0.004939894191920757, -2.9614076637526523),
        ("embed", 0.17133435606956482, -41.565092198779894),
        ("final_norm", 1.0640368461608887, 64.04061007499695),
        ("lm_head", 0.09291292726993561, 10.154092837897679),
    ],
    False: [
        ("blocks/0_attn/attn/wk", 0.019891798496246338, 1.8733147845532585),
        ("blocks/0_attn/attn/wo", 0.0012236695038154721, 8.30749055654087),
        ("blocks/0_attn/attn/wq", 0.254961758852005, -12.820626716917104),
        ("blocks/0_attn/attn/wv", -0.09005547314882278, 8.192175519969169),
        ("blocks/0_attn/ln1", 1.0092804431915283, 128.21842300891876),
        ("blocks/0_attn/ln2", 1.0590072870254517, 128.29960137605667),
        ("blocks/0_attn/mlp/wi", -0.009904091246426105, -16.421132586911654),
        ("blocks/0_attn/mlp/wo", 0.12315328419208527, 0.6663016833499569),
        ("embed", -0.10597340017557144, -33.080472731753844),
        ("final_norm", 1.0836652517318726, 64.56320208311081),
        ("lm_head", -0.03956101834774017, -35.498547563555064),
    ],
}


def _path(path) -> str:
    return "/".join(str(k.key) for k in path)


def _leaves(tree):
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree,
                                                   is_leaf=weights._is_leaf)
    return [(_path(p), leaf) for p, leaf in flat]


@pytest.mark.parametrize("name", ["qwen2.5-32b-8of64",
                                  "qwen2.5-32b-32of64-tp4"])
def test_dense_layout_is_unchanged(name):
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      name + ".json"))
    L = cfg["num_hidden_layers"]
    want = [(p, tuple(L if s == "L" else s for s in shape), dt, kind, std)
            for p, shape, dt, kind, std in QWEN_LEAVES]
    got = [(p,) + leaf for p, leaf in _leaves(spec.family(cfg).layout(cfg))]
    assert got == want


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "gelu"])
def test_dense_draw_is_unchanged(gated):
    import jax

    cfg = tiny.config(gated)
    tree = dense_gqa.layout(cfg)
    drawn = weights.drawer(tree)(weights.root_key(2 ** 31 + 5))
    flat, _ = jax.tree_util.tree_flatten_with_path(drawn)
    got = [(_path(p), np.asarray(a)) for p, a in flat]
    assert [p for p, _ in got] == [p for p, _, _ in TINY_DRAWS[gated]]
    for (p, a), (_, first, total) in zip(got, TINY_DRAWS[gated]):
        assert float(a.ravel()[0]) == first, p
        assert float(np.asarray(a, np.float64).sum()) == pytest.approx(
            total, rel=1e-12, abs=1e-12), p


def test_a_configuration_names_its_family():
    cfg = tiny.config(True)
    del cfg["family"]
    with pytest.raises(KeyError):
        spec.family(cfg)
    with pytest.raises(KeyError):
        spec.reference(cfg)


def test_every_configuration_file_names_a_family_with_both_files():
    for path in glob.glob(os.path.join(spec.BENCH_DIR, "configs", "*.json")):
        cfg = spec.load_json(path)
        for sub in ("families", "reference"):
            assert os.path.exists(os.path.join(
                spec.BENCH_DIR, sub, cfg["family"] + ".py")), path


# what the harness may read of a configuration file
HARNESS_KEYS = {"family", "torch_dtype", "program", "serve", "mesh",
                "limits"}


def _harness_files():
    return sorted(glob.glob(os.path.join(spec.BENCH_DIR, "harness", "*.py"))
                  + glob.glob(os.path.join(spec.BENCH_DIR, "metrics",
                                           "*.py")))


def test_the_harness_names_no_family_and_reads_no_model_key():
    """No file of the harness or the metrics names a family, imports a
    family's files, or holds a key of a configuration file other than
    the ones the harness reads."""
    families = {os.path.basename(p)[:-3] for p in glob.glob(os.path.join(
        spec.BENCH_DIR, "families", "*.py"))} - {"__init__"}
    model_keys = set()
    for path in glob.glob(os.path.join(spec.BENCH_DIR, "configs", "*.json")):
        model_keys |= set(spec.load_json(path))
    model_keys |= set(tiny.config(True)) | set(tiny.moe_config())
    model_keys -= HARNESS_KEYS
    files = _harness_files()
    assert len(files) > 10
    for path in files:
        with open(path) as f:
            text = f.read()
        for fam in families:
            assert fam not in text, (path, fam)
        tree = ast.parse(text)
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                assert not mod.startswith(("bench.families",
                                           "bench.reference")), path
            if isinstance(node, ast.Import):
                for alias in node.names:
                    assert not alias.name.startswith(
                        ("bench.families", "bench.reference")), path
            if isinstance(node, ast.Constant) and isinstance(node.value,
                                                             str):
                assert node.value not in model_keys, (path, node.value)


def test_a_family_brought_as_files_catches_an_altered_token(tmp_path):
    """The test family's own reference decides ``correct``: every decode
    step's greedy pick moved to the next id comes out not correct."""
    import time

    from bench.harness import runner

    d = tiny.write_bench_dir(tmp_path, {"moe-config": tiny.moe_config()},
                             {"mix": tiny.TRAFFIC}, families=("moe_gqa",),
                             family_dir=tiny.DATA)
    cell = spec.load_cell("moe.cell", d,
                          tiny.benchmark([("moe.cell", "moe-config", "mix")]))

    def alter(prog):
        eng, vocab = prog.engine, prog.dims.vocab
        orig = eng._argmax_multi
        eng._argmax_multi = lambda logits: (orig(logits) + 1) % vocab

    out = runner.run_cell(cell, 2 ** 31 + 13, 0.0, False, time.time(),
                          program_hook=alter, compile_cache=False)
    assert out["correct"] is False
