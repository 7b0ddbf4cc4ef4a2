"""Chunked gated linear attention (GLA) as a Pallas TPU kernel.

This is the recurrence core shared by Mamba2 (SSD) and xLSTM's mLSTM
(``repro.models.gla`` is the pure-jnp reference implementation used by the
models; ``ref.gla_ref`` is the O(S²) oracle).  TPU adaptation:

* the sequential chunk scan is the *last grid dimension*; the (dk × dv)
  state lives in VMEM scratch and carries across chunk steps — the HBM
  traffic per chunk is exactly q/k/v/g tiles in, y tile out,
* the intra-chunk part is two (L×L)·(L×d) MXU matmuls with a decay mask
  computed from an in-tile cumulative sum — chunk length L is the tiling
  knob that trades VMEM footprint against MXU utilization (ACTS tunes it),
* all gating math is performed as exp(difference-of-cumsums) in f32, so
  sigmoid/softplus log-decays never overflow.

Layout: one grid step owns one (batch, head) pair; heads are independent.
The kernel runs on head-major (B, H, S, d) views (the wrapper transposes
in and out) with the log-decay as a (B, H, S, 1) column, so every block's
last two dims are (chunk, full width) — Mosaic's (8, 128)-or-full-dim
rule holds at any head width.  Everything in the body stays 2-D, and
the in-chunk cumulative decays are triangular matmuls (Mosaic has no
cumsum).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .launch import launch_params

__all__ = ["gla_pallas"]


def _kernel(q_ref, k_ref, v_ref, g_ref, y_ref, state_out_ref, state_ref, *,
            chunk: int, seq: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (L, dk)
    k = k_ref[0, 0].astype(jnp.float32)  # (L, dk)
    v = v_ref[0, 0].astype(jnp.float32)  # (L, dv)
    g = g_ref[0, 0].astype(jnp.float32)  # (L, 1)

    # mask padding steps: zero decay, zero k contribution
    pos = ic * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    valid = pos < seq
    g = jnp.where(valid, g, 0.0)
    k = jnp.where(valid, k, 0.0)

    # Mosaic has no cumsum, so the in-chunk decay sums are triangular
    # matmuls at HIGHEST precision (a default-precision f32 matmul would
    # round g to bfloat16 first).  Each sum is taken directly over its own
    # steps, never as a difference of two prefix sums, so large decays
    # lose nothing to cancellation:
    #   c[t]    = sum_{u <= t} g_u        (prefix, column)
    #   tail[t] = sum_{u > t} g_u         (= c_L - c_t, column)
    #   seg[t, s] = sum_{s < u <= t} g_u  (= c_t - c_s for s <= t)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = rows >= cols  # (t, s): s <= t
    g_b = jnp.broadcast_to(g, (chunk, chunk))  # g_b[u, :] = g_u

    def tri_sum(mask, x):
        return jax.lax.dot(mask.astype(jnp.float32), x,
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)

    c = tri_sum(tri, g_b)[:, :1]  # (L, 1)
    tail = tri_sum(rows < cols, g_b)[:, :1]  # (L, 1)
    seg = tri_sum(tri, jnp.where(rows > cols, g_b, 0.0))  # (L, L)
    cL = jnp.sum(g, axis=0, keepdims=True)  # (1, 1) = c at the last step
    state = state_ref[...]  # (dk, dv)

    # inter-chunk: y += exp(c_t) · q_t S_in
    y_inter = jax.lax.dot(q * jnp.exp(c), state,
                          preferred_element_type=jnp.float32)
    # intra-chunk: decay matrix exp(c_t − c_s) for s ≤ t
    att = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    att = jnp.where(tri, att * jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    y = y_inter + jax.lax.dot(att, v, preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S = exp(c_L) S + Σ_s exp(c_L − c_s) k_s v_sᵀ
    k_dec = k * jnp.exp(tail)
    state_ref[...] = jnp.exp(cL) * state + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ic == nc - 1)
    def finalize():
        state_out_ref[0, 0] = state_ref[...]


def gla_pallas(
    q: jax.Array,  # (B, S, H, dk)
    k: jax.Array,  # (B, S, H, dk)
    v: jax.Array,  # (B, S, H, dv)
    log_g: jax.Array,  # (B, S, H)
    chunk: int = 128,
    dimension_semantics: Optional[str] = None,
    num_warps: Optional[int] = None,  # GPU-lowering hint; inert on TPU
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y, final_state (B,H,dk,dv) f32). Zero initial state."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        padq = ((0, 0), (0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, padq)
        k = jnp.pad(k, padq)
        v = jnp.pad(v, padq)
        log_g = jnp.pad(log_g, ((0, 0), (0, pad), (0, 0)))
    nc = q.shape[1] // chunk
    # head-major views: (B, S, H, d) -> (B, H, S, d); decay as a column
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    log_g = log_g.transpose(0, 2, 1)[..., None]

    # the chunk dim carries the recurrent state scratch; B/H parallel
    params = launch_params(dimension_semantics, 3, 1, interpret)
    del num_warps
    y, state = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, seq=S),
        grid=(B, H, nc),
        **({"compiler_params": params} if params else {}),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dk), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, dv), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, ic: (b, h, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, dv), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((B, H, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(q, k, v, log_g)
    return y.transpose(0, 2, 1, 3)[:, :S], state
