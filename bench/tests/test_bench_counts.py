"""FLOP and byte counts of the dense GQA family against hand counts at a
small shape, per chip under a mesh; peaks."""
import pytest

from bench.families import dense_gqa as counts
from bench.harness import peaks

DIMS = counts.Dims(d=8, heads=4, kv_heads=2, head_dim=2, ff=16, vocab=32,
                   layers=3, gated=True)


def test_layer_matmul_params_by_hand():
    # q 8x4x2 + k 8x2x2 + v 8x2x2 + o 4x2x8 = 64+32+32+64; MLP 3x8x16
    assert DIMS.layer_matmul_params == 192 + 384


def test_token_flops_are_two_per_param_plus_attention():
    # 3 layers x (2 x 576 + 4 heads x 2 dims x 4 (QK and PV) x 10 keys)
    assert counts.token_flops(DIMS, 10) == 3 * (2 * 576 + 4 * 4 * 2 * 10)


def test_prefill_and_decode_add_up_token_by_token():
    P, n = 5, 4
    by_token = sum(counts.token_flops(DIMS, p + 1) for p in range(P))
    by_token += counts.head_flops(DIMS)  # logits of the last prompt token
    assert counts.prefill_flops(DIMS, P) == by_token
    dec = sum(counts.token_flops(DIMS, P + i) + counts.head_flops(DIMS)
              for i in range(1, n))
    assert counts.decode_flops(DIMS, P, n) == dec
    assert counts.request_flops(DIMS, P, n) == by_token + dec
    assert counts.head_flops(DIMS) == 2 * 8 * 32


def test_one_decode_token_kv_bytes_by_hand():
    # prompt 7, 2 new tokens: one decode step, feeding position 7 and
    # attending 8 keys: K and V of 8 tokens x 2 KV heads x 2 dims x 2 B,
    # q in and out 4 heads x 2 dims x 2 B each, per layer
    nbytes, flops = counts.paged_kernel_work(DIMS, 7, 2, 1)
    assert nbytes == 3 * (2 * 8 * 2 * 2 * 2 + 2 * 4 * 2 * 2)
    assert flops == 3 * 4 * 4 * 2 * 8


def test_first_token_needs_no_decode():
    assert counts.paged_kernel_work(DIMS, 7, 1, 1) == (0, 0)
    assert counts.decode_flops(DIMS, 7, 1) == 0


def test_published_qwen_width_parameter_count():
    """5.458 B parameters at 8 layers of Qwen2.5-32B (matmuls, embedding
    and head; norms and biases are under 0.01%)."""
    d = counts.Dims(d=5120, heads=40, kv_heads=8, head_dim=128, ff=27648,
                    vocab=152064, layers=8, gated=True)
    n = d.layers * d.layer_matmul_params + 2 * d.vocab * d.d
    assert n == pytest.approx(5.458e9, rel=1e-3)


QWEN = counts.Dims(d=5120, heads=40, kv_heads=8, head_dim=128, ff=27648,
                   vocab=152064, layers=32, gated=True)


@pytest.mark.parametrize("prompt,new", [(1024, 129), (64, 16), (3584, 512)])
def test_four_shards_count_a_quarter_of_the_kernel_work(prompt, new):
    """Under TP (1, 4) each chip's kernel streams 2 of the 8 KV heads and
    their 10 q heads: exactly a quarter of the bytes and operations."""
    whole = counts.paged_kernel_work(QWEN, prompt, new, 1)
    shard = counts.paged_kernel_work(QWEN, prompt, new, 4)
    assert shard[0] * 4 == whole[0] and shard[1] * 4 == whole[1]
    n = new - 1
    ctx = counts.sum_contexts(prompt + 1, prompt + n)
    assert shard[0] == 32 * (2 * 2 * 128 * 2 * ctx + 2 * 10 * 128 * 2 * n)


def test_heads_that_do_not_split_are_refused():
    with pytest.raises(ValueError):
        counts.paged_kernel_work(QWEN, 64, 16, 3)


def test_v5e_peaks_and_unknown_device():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_s"] == 197e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
