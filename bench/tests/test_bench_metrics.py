"""Each per-layer reader on a hand-made trace and hand-made counters."""
from types import SimpleNamespace

import pytest

from bench.families import dense_gqa as counts
from bench.harness import spec
from bench.harness.runner import Run
from bench.harness.trace import Trace

DIMS = counts.Dims(d=8, heads=4, kv_heads=2, head_dim=2, ff=16, vocab=32,
                   layers=3, gated=True)
PEAKS = {"bf16_flops_s": 1e12, "hbm_bytes_s": 1e9}
# window 0..1000 ns; chip 0: two decode steps (10..110, 200..300; the
# kernel 50..90 and 250..280, another custom call 95..100) and one
# prefill program 400..600; chip 1:
# busy 0..500 with an all-reduce 100..200
TRACE = {
    "window": [0, 1000],
    "devices": [
        {"name": "/device:TPU:0",
         "modules": [["jit_decode_step_multi(7)", 10, 100, ""],
                     ["jit_decode_step_multi(7)", 200, 100, ""],
                     ["jit_prefill_chunk_slot_paged(9)", 400, 200, ""]],
         "ops": [["fusion.1", 10, 100, "jit_decode_step_multi(7)"],
                 ["_paged_flash_decode.9", 50, 40,
                  "jit_decode_step_multi(7)"],
                 ["custom-call.20", 95, 5, "jit_decode_step_multi(7)"],
                 ["fusion.1", 200, 100, "jit_decode_step_multi(7)"],
                 ["_paged_flash_decode.9", 250, 30,
                  "jit_decode_step_multi(7)"],
                 ["fusion.4", 400, 200, "jit_prefill_chunk_slot_paged(9)"]]},
        {"name": "/device:TPU:1", "modules": [],
         "ops": [["fusion.1", 0, 500, "jit_keyed(1)"],
                 ["all-reduce.2", 100, 100, "jit_keyed(1)"]]},
    ],
    "host": []}
# two requests: prompts 5 and 7, 3 and 2 new tokens, over 2 decode steps
RESULTS = [SimpleNamespace(steps=2, per_request=[
    {"prompt_len": 5, "new_tokens": 3, "ttft_s": 0.1, "latency_s": 0.3},
    {"prompt_len": 7, "new_tokens": 2, "ttft_s": 0.1, "latency_s": 0.2}])]


def _run(trace=TRACE, chips=2, model_shards=1):
    return Run(cell=None, dims=DIMS, chips=chips, peaks=PEAKS,
               results=RESULTS, trace=Trace(trace), family=counts,
               model_shards=model_shards)


def read(name, run=None):
    return spec.metric_reader(name)(run or _run())


def test_decode_batch_mean():
    assert read("decode_batch_mean") == pytest.approx((5 - 2) / 2)


def test_decode_step_ms():
    assert read("decode_step_ms") == pytest.approx(100e-6)


def test_prefill_ms_per_ktok():
    # 200 ns of prefill over 12 prompt tokens
    assert read("prefill_ms_per_ktok") == pytest.approx(200e-6 / 0.012)


def test_paged_attn_roofline():
    b1, f1 = counts.paged_kernel_work(DIMS, 5, 3, 1)
    b2, f2 = counts.paged_kernel_work(DIMS, 7, 2, 1)
    least = max((b1 + b2) / 1e9, (f1 + f2) / 1e12)
    assert read("paged_attn_roofline") == pytest.approx(
        100 * least / 70e-9)


def test_paged_attn_roofline_counts_chip_0s_shard():
    """Under a (1, 2) mesh chip 0's kernel streams half the heads, so the
    same kernel time reads half the share."""
    whole = read("paged_attn_roofline")
    assert read("paged_attn_roofline", _run(model_shards=2)) == \
        pytest.approx(whole / 2)


def test_mfu_counts_the_whole_model_under_a_mesh():
    assert read("mfu", _run(model_shards=2)) == read("mfu")


def test_device_idle_share():
    # chip 0 busy 10..110, 200..300, 400..600 = 400; chip 1 busy 500
    assert read("device_idle_share") == pytest.approx(100 * (1 - 450 / 1000))


def test_mfu():
    flops = counts.request_flops(DIMS, 5, 3) + counts.request_flops(
        DIMS, 7, 2)
    assert read("mfu") == pytest.approx(100 * flops / (1e-6 * 2 * 1e12))


def test_collective_share_on_the_busiest_chip():
    assert read("collective_share") == pytest.approx(100 * 100 / 500)


def test_readers_that_find_nothing_return_nothing():
    keyed = {"window": [0, 100], "host": [], "devices": [
        {"name": "/device:TPU:0", "modules": [["jit_keyed(1)", 0, 50, ""]],
         "ops": [["fusion", 0, 50, "jit_keyed(1)"]]}]}
    run = _run(keyed, chips=1)
    for name in ("decode_step_ms", "prefill_ms_per_ktok",
                 "paged_attn_roofline", "collective_share"):
        assert read(name, run) is None, name
    assert read("device_idle_share", run) == pytest.approx(50.0)
