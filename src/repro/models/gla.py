"""Chunked gated linear attention (GLA) — the shared recurrence core.

Both Mamba2's SSD and xLSTM's mLSTM are instances of the same primitive:

    S_t = exp(g_t) · S_{t-1} + k_t v_tᵀ          (state: dk × dv per head)
    y_t = q_tᵀ S_t

with per-step, per-head log-decay ``g_t ≤ 0``.  We evaluate it chunkwise —
within a chunk the quadratic "attention" form with decay matrix
``exp(c_t − c_s)`` (c = inclusive cumsum of g), across chunks a scan carries
the state — which is the TPU-native way to run these models: the chunk
matmuls hit the MXU, the scan is O(S/chunk).  This file is the pure-jnp
reference; ``repro.kernels`` provides the Pallas TPU kernel for the same
computation.

All math is f32 internally; decays are exponentiated only as sums of
log-decays over the steps they span (never a product of exponentials, so
nothing overflows, and never a difference of prefix sums, so nothing
cancels).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["chunked_gla", "gla_step"]


def chunked_gla(
    q: jax.Array,  # (B, S, H, dk)
    k: jax.Array,  # (B, S, H, dk)
    v: jax.Array,  # (B, S, H, dv)
    log_g: jax.Array,  # (B, S, H) per-step log decay (≤ 0)
    chunk: int = 256,
    initial_state: Optional[jax.Array] = None,  # (B, H, dk, dv)
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y: (B,S,H,dv), final_state: (B,H,dk,dv))."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    L = min(chunk, S)
    pad = (-S) % L
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        log_g = jnp.pad(log_g, ((0, 0), (0, pad), (0, 0)))
    nc = (S + pad) // L

    f32 = jnp.float32
    qc = q.reshape(B, nc, L, H, dk).astype(f32)
    kc = k.reshape(B, nc, L, H, dk).astype(f32)
    vc = v.reshape(B, nc, L, H, dv).astype(f32)
    gc = log_g.reshape(B, nc, L, H).astype(f32)

    S0 = (initial_state.astype(f32) if initial_state is not None
          else jnp.zeros((B, H, dk, dv), f32))

    def one_chunk(state, inputs):
        qb, kb, vb, gb = inputs  # (B,L,H,·)
        c = jnp.cumsum(gb, axis=1)  # inclusive cumsum (B,L,H)
        # segment sums seg[t, s] = Σ_{s<u≤t} g_u = c_t − c_s, summed over
        # their own steps: as a difference of two prefix sums they would
        # cancel catastrophically once |c| grows (mamba2 log-decays reach
        # -100 per step, so c reaches -1e3 within a chunk)
        tri = jnp.tril(jnp.ones((L, L), bool))
        strict = jnp.tril(jnp.ones((L, L), bool), -1)  # s < t
        seg = jnp.cumsum(jnp.where(strict[None, :, :, None],
                                   gb[:, :, None, :], 0.0), axis=1)
        # inter-chunk: y += exp(c_t) · qᵀ S_in
        y_inter = jnp.einsum("blhk,bhkv->blhv", qb * jnp.exp(c)[..., None], state)
        # intra-chunk: decay matrix exp(c_t − c_s), s ≤ t
        dmat = jnp.where(tri[None, :, :, None], seg, -jnp.inf)  # (B,t,s,H)
        att = jnp.einsum("blhk,bmhk->blmh", qb, kb) * jnp.exp(dmat)
        y_intra = jnp.einsum("blmh,bmhv->blhv", att, vb)
        # state out: S = exp(c_L) S_in + Σ_s exp(c_L − c_s) k_s v_sᵀ
        cL = c[:, -1, :]  # (B,H)
        carry_decay = jnp.exp(cL)[:, :, None, None]
        k_decay = jnp.exp(seg[:, -1])  # (B,L,H): c_L − c_s per step s
        state_new = carry_decay * state + jnp.einsum(
            "blhk,blhv->bhkv", kb * k_decay[..., None], vb)
        return state_new, y_inter + y_intra

    state, ys = jax.lax.scan(
        one_chunk, S0,
        (qc.swapaxes(0, 1), kc.swapaxes(0, 1), vc.swapaxes(0, 1),
         gc.swapaxes(0, 1)),
    )
    y = ys.swapaxes(0, 1).reshape(B, nc * L, H, dv)[:, :S - 0 if not pad else S]
    y = y[:, :S]
    return y.astype(v.dtype), state


def gla_step(
    q: jax.Array,  # (B, H, dk)
    k: jax.Array,  # (B, H, dk)
    v: jax.Array,  # (B, H, dv)
    log_g: jax.Array,  # (B, H)
    state: jax.Array,  # (B, H, dk, dv)
) -> Tuple[jax.Array, jax.Array]:
    """Single-token recurrent update (decode path). O(dk·dv) per head."""
    f32 = jnp.float32
    decay = jnp.exp(log_g.astype(f32))[..., None, None]
    state_new = decay * state.astype(f32) + jnp.einsum(
        "bhk,bhv->bhkv", k.astype(f32), v.astype(f32))
    y = jnp.einsum("bhk,bhkv->bhv", q.astype(f32), state_new)
    return y.astype(v.dtype), state_new
