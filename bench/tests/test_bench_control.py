"""The control through the run's own comparison: a whole run at a middle
width in bfloat16 on the CPU, judged against the chat cell's own limit.
The served tokens pass; the fp8 reference's picks, put in their place,
come out not correct."""
import time

import pytest

import tiny
from bench.harness import runner, spec
from repro.configs import ModelConfig, get_config, register

CELL = "qwen2.5-32b-8of64.chat"
D, HEADS, KV, FF, VOCAB, LAYERS = 1024, 8, 2, 2816, 8192, 4


def _mid_cell(tmp):
    name = "bench-mid-bf16"
    try:
        get_config(name)
    except KeyError:
        register(ModelConfig(
            name=name, family="dense", n_layers=LAYERS, d_model=D,
            n_heads=HEADS, n_kv_heads=KV, d_ff=FF, vocab_size=VOCAB,
            superblock=("attn",), activation="swiglu", qkv_bias=True,
            rope_theta=1e6, tie_embeddings=False, vocab_pad_multiple=256))
    limits = spec.load_cell(CELL).config["limits"]
    cfg = tiny.config(True)
    cfg.update(hidden_size=D, num_attention_heads=HEADS,
               num_key_value_heads=KV, intermediate_size=FF,
               vocab_size=VOCAB, num_hidden_layers=LAYERS,
               torch_dtype="bfloat16", rope_theta=1e6, limits=limits,
               program={"registry": name, "embed_scale": True})
    cfg["serve"].update(batch_slots=4, max_seq=512, prefill_chunk=128,
                        kv_page_block=4)
    mix = {"kind": "closed_lognormal", "batch": 6,
           "prompt": {"median": 192, "sigma": 0.5, "min": 64, "max": 384,
                      "grid": 64},
           "output": {"median": 40, "sigma": 0.5, "min": 16, "max": 96},
           "check_requests": 4}
    d = tiny.write_bench_dir(tmp, {"mid": cfg}, {"mix": mix})
    return spec.load_cell("mid.mix", d,
                          tiny.benchmark([("mid.mix", "mid", "mix")]))


@pytest.mark.parametrize("control,correct", [(None, True), ("fp8", False)],
                         ids=["program", "fp8_control"])
def test_control_fails_the_cells_limit(tmp_path, control, correct):
    cell = _mid_cell(tmp_path)
    out = runner.run_cell(cell, 2 ** 31 + 77, 0.0, False, time.time(),
                          compile_cache=False, control=control)
    assert out["failed"] == 0
    assert out["correct"] is correct
    c = out["compared"]["widest_logit_gap"]
    assert c["limit"] == spec.load_cell(CELL).config["limits"][
        "widest_logit_gap"]
    assert list(out)[-1] == "compared"
