"""Dense GQA decoder (the Qwen2 block): what the harness needs of a
configuration of this family.

* ``dims(cfg)``: the sizes that traffic, metrics and counts read;
* ``program_config(name, cfg)``: the program's registry model at the
  file's depth, checked against every width of the file;
* ``layout(cfg)``: the weight tree as the program takes it, which
  ``bench/harness/weights.py`` draws: leaves stacked over layers under
  ``blocks/0_attn``, matmul weights in the type they are served in
  (``torch_dtype``), norm scales in float32.  Scales keep activations
  near unit size at any width: matmul weights ``N(0, 1/fan_in)``, norm
  scales ``1 + N(0, 0.05^2)``, biases ``N(0, 0.1^2)``, and the embedding
  ``N(0, 1/d)`` (the program multiplies it by ``sqrt(d)``);
* ``request_flops`` and ``paged_kernel_work``: operations and bytes that
  the work needs, counted from its shapes.

The counts are the algorithm's, not what the program happens to
execute: a decode token at position ``p`` attends ``p + 1`` keys, a
prompt token at position ``p`` attends ``p + 1`` keys, and the LM head
counts only for tokens whose logits are needed (each request's last
prompt token and each decode token).  Norms, biases, rotary embeddings
and the softmax are left out: at these widths they are well under 0.1%
of the matmul operations.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

# the published config's keys as the program's ModelConfig names them
_WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
           "vocab_size": "vocab_size", "rope_theta": "rope_theta",
           "qkv_bias": "qkv_bias", "tie_word_embeddings": "tie_embeddings"}
_ACTS = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}


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


def dims(cfg: Dict[str, Any]) -> Dims:
    return Dims.from_config(cfg)


def program_config(name: str, cfg: Dict[str, Any]):
    """The registry's model at this file's depth and norm epsilon; every
    other field must already equal the file, or this raises."""
    from repro.configs import get_config

    base = get_config(cfg["program"]["registry"])
    eps = cfg.get("rms_norm_eps", cfg.get("norm_epsilon"))
    mcfg = dataclasses.replace(base, name=name,
                               n_layers=cfg["num_hidden_layers"],
                               norm_eps=float(eps),
                               param_dtype=cfg["torch_dtype"],
                               compute_dtype=cfg["torch_dtype"])
    want = {field: cfg[key] for key, field in _WIDTHS.items()}
    want["activation"] = _ACTS[cfg["hidden_act"]]
    want["superblock"] = ("attn",)
    got = {k: getattr(mcfg, k) for k in want}
    if got != want or mcfg.padded_heads != mcfg.n_heads:
        raise ValueError(f"{name}: the program's {base.name} is not this "
                         f"file: {got} != {want}")
    return mcfg


def layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Each leaf is ``(shape, dtype, kind, std)``; the drawer folds the
    key by leaf index, so the order of the leaves is part of the draw."""
    dm = dims(cfg)
    L, d, H, KV, Dh, F, V = (dm.layers, dm.d, dm.heads, dm.kv_heads,
                             dm.head_dim, dm.ff, dm.vocab)
    bf = cfg["torch_dtype"]
    attn = {
        "wq": ((L, d, H, Dh), bf, "normal", 1 / math.sqrt(d)),
        "wk": ((L, d, KV, Dh), bf, "normal", 1 / math.sqrt(d)),
        "wv": ((L, d, KV, Dh), bf, "normal", 1 / math.sqrt(d)),
        "wo": ((L, H, Dh, d), bf, "normal", 1 / math.sqrt(H * Dh)),
    }
    if cfg["qkv_bias"]:
        attn["bq"] = ((L, H, Dh), bf, "normal", 0.1)
        attn["bk"] = ((L, KV, Dh), bf, "normal", 0.1)
        attn["bv"] = ((L, KV, Dh), bf, "normal", 0.1)
    mlp = {"wi": ((L, d, F), bf, "normal", 1 / math.sqrt(d)),
           "wo": ((L, F, d), bf, "normal", 1 / math.sqrt(F))}
    if dm.gated:
        mlp["wg"] = ((L, d, F), bf, "normal", 1 / math.sqrt(d))
    norm = ((L, d), "float32", "norm", 0.05)
    return {
        "embed": ((V, d), bf, "normal", 1 / math.sqrt(d)),
        "blocks": {"0_attn": {"ln1": norm, "attn": attn, "ln2": norm,
                              "mlp": mlp}},
        "final_norm": ((d,), "float32", "norm", 0.05),
        "lm_head": ((d, V), bf, "normal", 1 / math.sqrt(d)),
    }


def token_flops(dims: Dims, context: int) -> int:
    """One token through every layer, attending ``context`` keys; no head."""
    attn = 4 * dims.heads * dims.head_dim * context
    return dims.layers * (2 * dims.layer_matmul_params + attn)


def head_flops(dims: Dims) -> int:
    return 2 * dims.d * dims.vocab


def sum_contexts(lo: int, hi: int) -> int:
    """sum of c for c in [lo, hi]."""
    return 0 if hi < lo else (lo + hi) * (hi - lo + 1) // 2


def prefill_flops(dims: Dims, prompt_len: int) -> int:
    """The prompt: token p attends p + 1 keys; logits for the last one."""
    per = 2 * dims.layer_matmul_params * dims.layers * prompt_len
    attn = 4 * dims.heads * dims.head_dim * dims.layers * sum_contexts(
        1, prompt_len)
    return per + attn + head_flops(dims)


def decode_flops(dims: Dims, prompt_len: int, new_tokens: int) -> int:
    """The ``new_tokens - 1`` decode steps after the prefill's first token:
    step i feeds position ``prompt_len + i - 1`` and attends
    ``prompt_len + i`` keys, with logits for each."""
    n = new_tokens - 1
    per = (2 * dims.layer_matmul_params * dims.layers + head_flops(dims)) * n
    attn = 4 * dims.heads * dims.head_dim * dims.layers * sum_contexts(
        prompt_len + 1, prompt_len + n)
    return per + attn


def request_flops(dims: Dims, prompt_len: int, new_tokens: int) -> int:
    """The whole model's operations for one request, on all chips."""
    return (prefill_flops(dims, prompt_len)
            + decode_flops(dims, prompt_len, new_tokens))


def paged_kernel_work(dims: Dims, prompt_len: int, new_tokens: int,
                      model_shards: int) -> Tuple[int, int]:
    """(bytes, flops) of one chip's paged decode kernel over one request's
    decode steps, all layers: K and V of the valid context, q in and out,
    for the heads of one of ``model_shards`` tensor-parallel shards (each
    chip's kernel streams only its own KV heads and their q heads)."""
    if dims.heads % model_shards or dims.kv_heads % model_shards:
        raise ValueError(f"{dims.heads} heads and {dims.kv_heads} KV heads "
                         f"do not split {model_shards} ways")
    heads = dims.heads // model_shards
    kv_heads = dims.kv_heads // model_shards
    n = new_tokens - 1
    ctx = sum_contexts(prompt_len + 1, prompt_len + n)
    kv = 2 * kv_heads * dims.head_dim * dims.act_bytes * ctx
    qo = 2 * heads * dims.head_dim * dims.act_bytes * n
    flops = 4 * heads * dims.head_dim * ctx
    return dims.layers * (kv + qo), dims.layers * flops
