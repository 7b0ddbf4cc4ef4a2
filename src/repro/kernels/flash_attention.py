"""Flash attention as a Pallas TPU kernel.

TPU adaptation (vs. the CUDA flash-attention algorithm):
* tiling is chosen for the MXU (128-aligned q/kv blocks) and VMEM residency —
  one (block_q × head_dim) query tile and one (block_kv × head_dim) KV tile
  live in VMEM per grid step; the online-softmax running state (m, l, acc)
  sits in VMEM scratch and persists across the sequential kv grid dimension,
* the kv loop is a *grid dimension* (TPU grids iterate minor-to-major, so
  scratch carries across kv steps for a fixed query tile), not an in-kernel
  loop — this lets Mosaic double-buffer the HBM→VMEM streams of K and V,
* GQA is handled in the index maps (kv head = q head // group), so KV tiles
  are fetched once per group without materializing repeated heads,
* the kernel runs on head-major (B, heads, S, D) views (the wrapper
  transposes in and out), so every block is a (block, D) slab whose last
  two dims meet Mosaic's (8, 128)-or-full-dim rule at any head_dim,
* causal + sliding-window masking short-circuits fully-masked tiles with
  ``pl.when`` (block-level skip ≈ the CUDA early-exit) — causal attention
  does ~half the tile work of the full square.

Numerics: f32 accumulation regardless of input dtype; output cast back.
Validated on CPU in interpret mode against ``ref.attention_ref``.
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

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_kv: int, seq_kv: int, causal: bool,
            window: int, q_offset: int, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = q_offset + iq * block_q
    k_start = ik * block_kv
    # Block-level reachability: skip tiles fully above the causal diagonal
    # or fully left of the sliding window.
    reachable = k_start < seq_kv
    if causal:
        reachable &= k_start <= q_start + block_q - 1
    if window:
        reachable &= k_start + block_kv - 1 > q_start - window

    @pl.when(reachable)
    def compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (bkv, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_kv
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]  # (bq, 1)
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


def flash_attention_pallas(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,  # (B, Sk, KV, D)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    dimension_semantics: Optional[str] = None,
    num_warps: Optional[int] = None,  # GPU-lowering hint; inert on TPU
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    if H % KV:
        raise ValueError(f"H={H} not a multiple of KV={KV}")
    group = H // KV
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Sk)

    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq = q.shape[1] // block_q
    nk = k.shape[1] // block_kv
    # head-major views: (B, S, heads, D) -> (B, heads, S, D)
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))

    kernel = functools.partial(
        _kernel, block_q=block_q, block_kv=block_kv, seq_kv=Sk,
        causal=causal, window=window, q_offset=q_offset,
        scale=1.0 / math.sqrt(D))

    # the kv dim carries the online-softmax scratch; B/H/q-tiles parallel
    params = launch_params(dimension_semantics, 4, 1, interpret)
    del num_warps
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        **({"compiler_params": params} if params else {}),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),  # running denom l
            pltpu.VMEM((block_q, D), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out.transpose(0, 2, 1, 3)[:, :Sq]
