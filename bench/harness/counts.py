"""Operations and bytes that the work needs, counted from its shapes.

These are the algorithm's counts, not what the program happens to
execute: a decode token at position ``p`` attends ``p + 1`` keys, a
prompt token at position ``p`` attends ``p + 1`` keys, and the LM head
counts only for tokens whose logits are needed (each request's last
prompt token and each decode token).  Norms, biases, rotary embeddings
and the softmax are left out: at these widths they are well under 0.1%
of the matmul operations.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple


@dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    gated: bool
    act_bytes: int = 2  # bfloat16 K/V, q and out

    @classmethod
    def from_config(cls, cfg: Dict[str, Any]) -> "Dims":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(d=d, heads=h, kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim") or d // h,
                   ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                   layers=cfg["num_hidden_layers"],
                   gated=cfg["hidden_act"] == "silu")

    @property
    def layer_matmul_params(self) -> int:
        attn = (2 * self.heads + 2 * self.kv_heads) * self.head_dim * self.d
        mlp = (3 if self.gated else 2) * self.d * self.ff
        return attn + mlp


def token_flops(dims: Dims, context: int) -> int:
    """One token through every layer, attending ``context`` keys; no head."""
    attn = 4 * dims.heads * dims.head_dim * context
    return dims.layers * (2 * dims.layer_matmul_params + attn)


def head_flops(dims: Dims) -> int:
    return 2 * dims.d * dims.vocab


def _sum_contexts(lo: int, hi: int) -> int:
    """sum of c for c in [lo, hi]."""
    return 0 if hi < lo else (lo + hi) * (hi - lo + 1) // 2


def prefill_flops(dims: Dims, prompt_len: int) -> int:
    """The prompt: token p attends p + 1 keys; logits for the last one."""
    per = 2 * dims.layer_matmul_params * dims.layers * prompt_len
    attn = 4 * dims.heads * dims.head_dim * dims.layers * _sum_contexts(
        1, prompt_len)
    return per + attn + head_flops(dims)


def decode_flops(dims: Dims, prompt_len: int, new_tokens: int) -> int:
    """The ``new_tokens - 1`` decode steps after the prefill's first token:
    step i feeds position ``prompt_len + i - 1`` and attends
    ``prompt_len + i`` keys, with logits for each."""
    n = new_tokens - 1
    per = (2 * dims.layer_matmul_params * dims.layers + head_flops(dims)) * n
    attn = 4 * dims.heads * dims.head_dim * dims.layers * _sum_contexts(
        prompt_len + 1, prompt_len + n)
    return per + attn


def request_flops(dims: Dims, prompt_len: int, new_tokens: int) -> int:
    return (prefill_flops(dims, prompt_len)
            + decode_flops(dims, prompt_len, new_tokens))


def paged_kernel_work(dims: Dims, prompt_len: int,
                      new_tokens: int) -> Tuple[int, int]:
    """(bytes, flops) of the paged decode kernel over one request's decode
    steps, all layers: K and V of the valid context, q in and out."""
    n = new_tokens - 1
    ctx = _sum_contexts(prompt_len + 1, prompt_len + n)
    kv = 2 * dims.kv_heads * dims.head_dim * dims.act_bytes * ctx
    qo = 2 * dims.heads * dims.head_dim * dims.act_bytes * n
    flops = 4 * dims.heads * dims.head_dim * ctx
    return dims.layers * (kv + qo), dims.layers * flops
