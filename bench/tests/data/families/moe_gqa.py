"""A second model family for the tests, brought as files only: a
mixture-of-experts decoder with GQA attention (the Mixtral block, no
window).  Attention is ``dense_gqa``'s; the MLP is ``num_local_experts``
SwiGLU experts of width ``intermediate_size``, of which each token takes
``num_experts_per_tok`` by a float32 router, gates renormalised over
those.  The program's router capacity has to drop no token
(``capacity_factor * experts_per_token >= n_experts``), as the reference
drops none."""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict

from bench.families import dense_gqa

paged_kernel_work = dense_gqa.paged_kernel_work  # the same attention


@dataclass(frozen=True)
class Dims(dense_gqa.Dims):
    experts: int = 0
    top_k: int = 0

    @property
    def layer_matmul_params(self) -> int:
        """Active per token: attention, the router, ``top_k`` experts."""
        attn = (2 * self.heads + 2 * self.kv_heads) * self.head_dim * self.d
        return attn + self.d * self.experts + self.top_k * 3 * self.d * self.ff


def dims(cfg: Dict[str, Any]) -> Dims:
    base = dense_gqa.Dims.from_config(cfg)
    return Dims(**dataclasses.asdict(base), experts=cfg["num_local_experts"],
                top_k=cfg["num_experts_per_tok"])


def request_flops(dims: Dims, prompt_len: int, new_tokens: int) -> int:
    return dense_gqa.request_flops(dims, prompt_len, new_tokens)


def program_config(name: str, cfg: Dict[str, Any]):
    from repro.configs import get_config

    base = get_config(cfg["program"]["registry"])
    mcfg = dataclasses.replace(base, name=name,
                               n_layers=cfg["num_hidden_layers"],
                               norm_eps=float(cfg["rms_norm_eps"]),
                               param_dtype=cfg["torch_dtype"],
                               compute_dtype=cfg["torch_dtype"])
    want = {"d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "vocab_size": cfg["vocab_size"], "rope_theta": cfg["rope_theta"],
            "qkv_bias": cfg["qkv_bias"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "activation": "swiglu", "superblock": ("moe",)}
    got = {k: getattr(mcfg, k) for k in want}
    moe = mcfg.moe
    experts = (cfg["num_local_experts"], cfg["num_experts_per_tok"],
               cfg["intermediate_size"])
    if (got != want or moe is None
            or (moe.n_experts, moe.experts_per_token, moe.d_ff) != experts
            or moe.capacity_factor * moe.experts_per_token < moe.n_experts):
        raise ValueError(f"{name}: the program's {base.name} is not this "
                         f"file: {got}, {moe} != {want}, {experts}")
    return mcfg


def layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    dm = dims(cfg)
    L, d, H, KV, Dh, F, V, E = (dm.layers, dm.d, dm.heads, dm.kv_heads,
                                dm.head_dim, dm.ff, dm.vocab, dm.experts)
    bf = cfg["torch_dtype"]
    attn = {
        "wq": ((L, d, H, Dh), bf, "normal", 1 / math.sqrt(d)),
        "wk": ((L, d, KV, Dh), bf, "normal", 1 / math.sqrt(d)),
        "wv": ((L, d, KV, Dh), bf, "normal", 1 / math.sqrt(d)),
        "wo": ((L, H, Dh, d), bf, "normal", 1 / math.sqrt(H * Dh)),
    }
    moe = {"router": ((L, d, E), "float32", "normal", 1 / math.sqrt(d)),
           "wi": ((L, E, d, F), bf, "normal", 1 / math.sqrt(d)),
           "wg": ((L, E, d, F), bf, "normal", 1 / math.sqrt(d)),
           "wo": ((L, E, F, d), bf, "normal", 1 / math.sqrt(F))}
    norm = ((L, d), "float32", "norm", 0.05)
    return {
        "embed": ((V, d), bf, "normal", 1 / math.sqrt(d)),
        "blocks": {"0_moe": {"ln1": norm, "attn": attn, "ln2": norm,
                             "moe": moe}},
        "final_norm": ((d,), "float32", "norm", 0.05),
        "lm_head": ((d, V), bf, "normal", 1 / math.sqrt(d)),
    }
