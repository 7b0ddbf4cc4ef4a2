"""Flash-decode Pallas kernel: one query token vs a long KV cache.

Decode attention is bandwidth-bound (stream the cache once, trivial
compute), so the kernel's job is to keep the cache read perfectly
sequential and VMEM-tiled while handling GQA and a *dynamic* valid length
(`kv_len`, the number of tokens written so far — decode caches are
pre-allocated at max_seq).

Layout: grid (B, KV-head, kv-blocks); all G query heads of a KV group are
processed together as a (G, D) tile so each cache block is read ONCE per
group (the GQA bandwidth win).  The kernel streams a head-major
(B, KV, S, D) view of the cache, so every block is a (block_kv, D) slab
whose last two dims satisfy Mosaic's (8, 128)-or-full-dim rule at any
head_dim; the wrapper transposes the caller's (B, S, KV, D) cache into
that view.  Online-softmax state (m, l, acc) lives in 2-D VMEM scratch
across the kv-block dimension; fully-invalid blocks are skipped with
``pl.when`` (so a cache filled to 2k of 32k only streams 2k).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .launch import launch_params

__all__ = ["flash_decode_pallas"]

NEG_INF = -1e30


def _kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_kv: int, scale: float):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    kv_len = kvlen_ref[0]

    @pl.when(ik == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = ik * block_kv

    @pl.when(k_start < kv_len)  # skip never-written cache tail
    def compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (bkv, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G,bkv)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]  # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_decode_pallas(
    q: jax.Array,  # (B, H, D) — one new token per sequence
    k: jax.Array,  # (B, S, KV, D) cache buffer
    v: jax.Array,  # (B, S, KV, D)
    kv_len: jax.Array,  # scalar int32: valid cache entries
    *,
    block_kv: int = 256,
    dimension_semantics: Optional[str] = None,
    num_warps: Optional[int] = None,  # GPU-lowering hint; inert on TPU
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    if H % KV:
        raise ValueError(f"H={H} not a multiple of KV={KV}")
    G = H // KV
    block_kv = min(block_kv, S)
    pad = (-S) % block_kv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nk = k.shape[1] // block_kv
    qg = q.reshape(B, KV, G, D)
    k = k.transpose(0, 2, 1, 3)  # head-major (B, KV, S, D) view
    v = v.transpose(0, 2, 1, 3)
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(1)

    # the kv-block dim carries the online-softmax scratch; B/KV parallel
    params = launch_params(dimension_semantics, 3, 1, interpret)
    del num_warps
    out = pl.pallas_call(
        functools.partial(_kernel, block_kv=block_kv,
                          scale=1.0 / math.sqrt(D)),
        grid=(B, KV, nk),
        **({"compiler_params": params} if params else {}),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # kv_len scalar
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len, qg, k, v)
    return out.reshape(B, H, D)
