"""A cell at test size: the program's dense GQA block at tiny widths in
float32, registered under its own name, with a small closed mix.  Also
the test family ``moe_gqa`` (``data/families``, ``data/reference``) at
tiny widths."""
import copy
import json
import os
import shutil

from repro.configs import ModelConfig, MoESpec, get_config, register

from bench.harness import spec

WIDTHS = {"hidden_size": 64, "intermediate_size": 96,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "vocab_size": 512}
# every width divides 4 ways, so a (1, 4) mesh splits heads and KV heads
TP_WIDTHS = dict(WIDTHS, num_attention_heads=8, num_key_value_heads=4)


def _register(name: str, gated: bool, widths: dict) -> None:
    try:
        get_config(name)
        return
    except KeyError:
        pass
    register(ModelConfig(
        name=name, family="dense", n_layers=2, d_model=64,
        n_heads=widths["num_attention_heads"],
        n_kv_heads=widths["num_key_value_heads"], d_ff=96, vocab_size=512,
        superblock=("attn",),
        activation="swiglu" if gated else "gelu", qkv_bias=gated,
        rope_theta=10_000.0, tie_embeddings=False, param_dtype="float32",
        compute_dtype="float32", vocab_pad_multiple=64))


def config(gated: bool = True, mesh=None) -> dict:
    widths = TP_WIDTHS if mesh else WIDTHS
    name = "bench-tiny-" + ("swiglu" if gated else "gelu") + (
        "-tp" if mesh else "")
    _register(name, gated, widths)
    cfg = dict(widths, family="dense_gqa", num_hidden_layers=2,
               hidden_act="silu" if gated else "gelu_pytorch_tanh",
               rope_theta=10000.0, rms_norm_eps=1e-6,
               tie_word_embeddings=False, torch_dtype="float32",
               qkv_bias=gated, program={"registry": name,
                                        "embed_scale": True},
               serve={"batch_slots": 4, "max_seq": 160, "prefill_chunk": 32,
                      "kv_page_block": 1, "runtime": "continuous",
                      "kv_layout": "paged", "schedule": "fifo",
                      "page_policy": "reserve", "temperature": 0.0},
               mesh=mesh, limits={"widest_logit_gap": 1e-3})
    return cfg


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def moe_config() -> dict:
    """The test family ``moe_gqa``: 4 experts, 2 a token, with a router
    capacity that drops no token."""
    name = "bench-tiny-moe"
    try:
        get_config(name)
    except KeyError:
        register(ModelConfig(
            name=name, family="moe", n_layers=2, d_model=64, n_heads=4,
            n_kv_heads=2, d_ff=96, vocab_size=512, superblock=("moe",),
            moe=MoESpec(n_experts=4, experts_per_token=2, d_ff=96,
                        capacity_factor=2.0),
            activation="swiglu", rope_theta=10_000.0, tie_embeddings=False,
            param_dtype="float32", compute_dtype="float32",
            vocab_pad_multiple=64))
    cfg = config(True)
    cfg.update(family="moe_gqa", qkv_bias=False, num_local_experts=4,
               num_experts_per_tok=2,
               program={"registry": name, "embed_scale": True})
    return cfg


TRAFFIC = {"kind": "closed_lognormal", "batch": 6,
           "prompt": {"median": 40, "sigma": 0.6, "min": 16, "max": 96,
                      "grid": 16},
           "output": {"median": 8, "sigma": 0.6, "min": 2, "max": 24},
           "check_requests": 3}


def benchmark(cells) -> dict:
    """A BENCHMARK.json-shaped dict for ``cells``: (name, config, mix)."""
    real = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    return {"end_to_end": copy.deepcopy(real["end_to_end"]),
            "per_layer": copy.deepcopy(real["per_layer"]),
            "workloads": [{"name": n, "config": c, "traffic": t, "chips": 1,
                           "why": "test"} for n, c, t in cells]}


def write_bench_dir(tmp, configs: dict, mixes: dict,
                    families=("dense_gqa",), family_dir=spec.BENCH_DIR
                    ) -> str:
    """A bench tree under ``tmp`` holding only these configs and mixes,
    and the files of these model families, copied from ``family_dir``."""
    for sub, files in (("configs", configs), ("traffic", mixes)):
        os.makedirs(os.path.join(tmp, sub), exist_ok=True)
        for name, body in files.items():
            with open(os.path.join(tmp, sub, name + ".json"), "w") as f:
                json.dump(body, f)
    for fam in families:
        for sub in ("families", "reference"):
            os.makedirs(os.path.join(tmp, sub), exist_ok=True)
            shutil.copy(os.path.join(family_dir, sub, fam + ".py"),
                        os.path.join(tmp, sub))
    return str(tmp)


def cell(gated: bool = True, mesh=None, tmp=None):
    import tempfile

    tmp = tmp or tempfile.mkdtemp()
    name = "tiny-" + ("swiglu" if gated else "gelu") + ("-tp" if mesh else "")
    d = write_bench_dir(tmp, {name: config(gated, mesh)}, {"mix": TRAFFIC})
    return spec.load_cell(name + ".mix", d,
                          benchmark([(name + ".mix", name, "mix")]))
