"""Plain forward pass of a dense GQA decoder, in float32, layer by layer.

Written from the published description of the Qwen2 block (pre-norm
residual; grouped-query attention with rotary embeddings on
the first and second halves of each head; a SwiGLU or tanh-GELU MLP),
with no cache, no batching and no kernel, and importing nothing of the
program.  Where the program departs from the published model, the
configuration file says so, and this reference follows the program
there: the embedding scale through ``Spec``, biases by their presence
in the weight tree, RMSNorm throughout.

Weights are the benchmark's own tree (``bench/harness/weights.py``),
read in place: each layer's bfloat16 leaves are cast up to float32
inside that layer's call, so the whole model is never held in float32.
Every matmul runs at ``Precision.HIGHEST``.  ``precision="fp8"`` is the
control: every matmul input rounded to float8 (e4m3), products summed
in float32.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("float32", "fp8")
Q_BLOCK = 512  # query rows per attention block: bounds the logits' memory
HEAD_BLOCKS = 8  # vocabulary blocks of the LM head, computed in turn


@dataclass(frozen=True)
class Spec:
    eps: float
    rope_theta: float
    gated: bool  # SwiGLU (silu gate) if True, tanh-GELU otherwise
    vocab: int
    embed_scale: bool  # embeddings multiplied by sqrt(d_model)

    @classmethod
    def from_config(cls, cfg: Dict) -> "Spec":
        eps = cfg.get("rms_norm_eps", cfg.get("norm_epsilon"))
        return cls(eps=float(eps), rope_theta=float(cfg["rope_theta"]),
                   gated=cfg["hidden_act"] == "silu",
                   vocab=int(cfg["vocab_size"]),
                   embed_scale=bool(cfg["program"]["embed_scale"]))


def _round(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x.astype(jnp.float32)


def _mm(eq, a, b, precision):
    return jnp.einsum(eq, _round(a, precision), _round(b, precision),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """x: (S, heads, D); rotates (first half, second half) pairs."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, precision):
    """Causal GQA: q (S, H, D); k, v (S, KV, D) -> (S, H, D)."""
    S, H, D = q.shape
    KV = k.shape[1]
    qg = q.reshape(S, KV, H // KV, D) / math.sqrt(D)
    outs = []
    for lo in range(0, S, Q_BLOCK):
        hi = min(S, lo + Q_BLOCK)
        s = _mm("qkgd,tkd->kgqt", qg[lo:hi], k, precision)
        qpos = jnp.arange(lo, hi)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= qpos, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(_mm("kgqt,tkd->qkgd", p, v, precision))
    return jnp.concatenate(outs, 0).reshape(S, H, D)


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _layer(x, blocks, l, spec: Spec, precision: str):
    p = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
        blocks["0_attn"])
    a = p["attn"]
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms_norm(x, p["ln1"], spec.eps)
    q = _mm("sd,dhk->shk", h, a["wq"], precision)
    k = _mm("sd,dhk->shk", h, a["wk"], precision)
    v = _mm("sd,dhk->shk", h, a["wv"], precision)
    if "bq" in a:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    q = _rope(q, pos, spec.rope_theta)
    k = _rope(k, pos, spec.rope_theta)
    y = _attention(q, k, v, precision)
    x = x + _mm("shk,hkd->sd", y, a["wo"], precision)
    h = _rms_norm(x, p["ln2"], spec.eps)
    m = p["mlp"]
    up = _mm("sd,df->sf", h, m["wi"], precision)
    if spec.gated:
        up = jax.nn.silu(_mm("sd,df->sf", h, m["wg"], precision)) * up
    else:
        up = jax.nn.gelu(up, approximate=True)
    return x + _mm("sf,fd->sd", up, m["wo"], precision)


@functools.partial(jax.jit, static_argnames=("spec",))
def _embed(embed, tokens, spec: Spec):
    x = jnp.take(embed, tokens, axis=0).astype(jnp.float32)
    if spec.embed_scale:
        x = x * math.sqrt(embed.shape[1])
    return x


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _head(x, rows, final_norm, lm_head, spec: Spec, precision: str):
    """Logits of ``rows``, one vocabulary block at a time, so that only
    one block of the head is ever held in float32."""
    h = _rms_norm(x[rows], final_norm, spec.eps)
    n_blk = HEAD_BLOCKS if spec.vocab % HEAD_BLOCKS == 0 else 1
    width = spec.vocab // n_blk

    def block(i):
        w = jax.lax.dynamic_slice_in_dim(lm_head, i * width, width, axis=1)
        return _mm("nd,dv->nv", h, w, precision)

    out = jax.lax.map(block, jnp.arange(n_blk))  # (n_blk, n, width)
    return out.transpose(1, 0, 2).reshape(h.shape[0], spec.vocab)


def logits_at(weights, spec: Spec, tokens: Sequence[int],
              rows: Sequence[int], precision: str = "float32",
              bucket: int = 1024) -> jax.Array:
    """Float32 logits (len(rows), vocab) at positions ``rows`` of one
    sequence.  The sequence is padded to a multiple of ``bucket`` tokens
    (causal attention: the padding never reaches an earlier position),
    so a handful of shapes compile."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    n = len(tokens)
    S = -(-n // bucket) * bucket
    ids = np.zeros(S, np.int32)
    ids[:n] = tokens
    x = _embed(weights["embed"], jnp.asarray(ids), spec)
    blocks = weights["blocks"]
    L = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    for l in range(L):
        x = _layer(x, blocks, jnp.asarray(l, jnp.int32), spec, precision)
    return _head(x, jnp.asarray(np.asarray(rows, np.int32)),
                 weights["final_norm"], weights["lm_head"], spec, precision)


def gaps(ref_logits, tokens) -> jax.Array:
    """Per row: how far the logit of ``tokens[i]`` lies below the row's
    best, in the reference's logits."""
    t = jnp.asarray(np.asarray(tokens, np.int32))
    picked = jnp.take_along_axis(ref_logits, t[:, None], axis=1)[:, 0]
    return ref_logits.max(axis=1) - picked
