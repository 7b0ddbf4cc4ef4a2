"""Plain forward pass of the test family ``moe_gqa`` in float32: the
dense GQA reference's attention, then every expert on every token,
weighted by the renormalised top-k gates of a float32 router.  Imports
nothing of the program."""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dense_gqa as base

gaps = base.gaps


@dataclass(frozen=True)
class Spec:
    eps: float
    rope_theta: float
    vocab: int
    embed_scale: bool
    top_k: int

    @classmethod
    def from_config(cls, cfg: Dict) -> "Spec":
        return cls(eps=float(cfg["rms_norm_eps"]),
                   rope_theta=float(cfg["rope_theta"]),
                   vocab=int(cfg["vocab_size"]),
                   embed_scale=bool(cfg["program"]["embed_scale"]),
                   top_k=int(cfg["num_experts_per_tok"]))


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _layer(x, blocks, l, spec: Spec, precision: str):
    p = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
        blocks["0_moe"])
    a, mm = p["attn"], base._mm
    pos = jnp.arange(x.shape[0])
    h = base._rms_norm(x, p["ln1"], spec.eps)
    q = base._rope(mm("sd,dhk->shk", h, a["wq"], precision), pos,
                   spec.rope_theta)
    k = base._rope(mm("sd,dhk->shk", h, a["wk"], precision), pos,
                   spec.rope_theta)
    v = mm("sd,dhk->shk", h, a["wv"], precision)
    x = x + mm("shk,hkd->sd", base._attention(q, k, v, precision), a["wo"],
               precision)
    h = base._rms_norm(x, p["ln2"], spec.eps)
    m = p["moe"]
    probs = jax.nn.softmax(jnp.einsum("sd,de->se", h, m["router"],
                                      precision=base.HIGHEST), axis=-1)
    top, idx = jax.lax.top_k(probs, spec.top_k)
    top = top / top.sum(-1, keepdims=True)
    gate = (jax.nn.one_hot(idx, probs.shape[-1]) * top[..., None]).sum(1)
    up = jax.nn.silu(mm("sd,edf->sef", h, m["wg"], precision)) * mm(
        "sd,edf->sef", h, m["wi"], precision)
    y = mm("sef,efd->sed", up, m["wo"], precision)
    return x + jnp.einsum("sed,se->sd", y, gate, precision=base.HIGHEST)


def logits_at(weights, spec: Spec, tokens: Sequence[int],
              rows: Sequence[int], precision: str = "float32",
              bucket: int = 1024) -> jax.Array:
    if precision not in base.PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {base.PRECISIONS}")
    n = len(tokens)
    ids = np.zeros(-(-n // bucket) * bucket, np.int32)
    ids[:n] = tokens
    x = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(
        jnp.float32)
    if spec.embed_scale:
        x = x * math.sqrt(weights["embed"].shape[1])
    blocks = weights["blocks"]
    for l in range(jax.tree_util.tree_leaves(blocks)[0].shape[0]):
        x = _layer(x, blocks, jnp.asarray(l, jnp.int32), spec, precision)
    h = base._rms_norm(x[jnp.asarray(np.asarray(rows, np.int32))],
                       weights["final_norm"], spec.eps)
    return base._mm("nd,dv->nv", h, weights["lm_head"], precision)
