"""Attention: GQA/MQA/MHA, causal, sliding-window, cross — with KV caches.

Three interchangeable inner implementations (``cfg.attn_impl``):

* ``dense``   — materialized logits; reference semantics, smoke tests.
* ``blocked`` — flash-style online-softmax over KV blocks in pure JAX
                (O(S·block) memory); the default for long sequences.
* ``local``   — banded chunk attention for sliding-window layers:
                each Q chunk attends its own + previous chunk only
                (compute O(S·2W) instead of O(S²)).
* the Pallas TPU kernel (``repro.kernels``) plugs in via ``pallas`` and is
  numerically validated against ``dense`` in interpret mode.

All softmax math runs in f32 regardless of activation dtype.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.dist.sharding import constrain
from repro.models.common import (
    ParamDef,
    apply_rope,
    fan_in_init,
    rope_freqs,
    zeros_init,
)

__all__ = [
    "attention_defs",
    "self_attention",
    "cross_attention",
    "init_attn_cache_defs",
    "attend",
]

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameter definitions
# ---------------------------------------------------------------------------
def _valid_head_mask(cfg: ModelConfig):
    """(padded_heads,) bool — which padded head slots are real.

    Pads are interleaved *within* each GQA group (slot h belongs to kv group
    h // G_pad), so real heads keep their original kv-head assignment."""
    H_pad, KV = cfg.padded_heads, cfg.n_kv_heads
    g_pad, g_orig = H_pad // KV, cfg.n_heads // KV
    return (jnp.arange(H_pad) % g_pad) < g_orig


def _head_padded_init(base, cfg: ModelConfig, head_axis: int):
    """Zero the padded head slots so they contribute exactly 0 (their q rows
    and wo rows are zero => exact semantics)."""

    def init(key, shape, dtype):
        # head_axis is negative: superblock stacking prepends a layer dim
        w = base(key, shape, dtype)
        mask = _valid_head_mask(cfg)
        bc = [1] * len(shape)
        bc[head_axis] = shape[head_axis]
        return (w * mask.reshape(bc).astype(w.dtype)).astype(dtype)

    return init


def attention_defs(cfg: ModelConfig, cross: bool = False) -> Dict[str, ParamDef]:
    d, KV, Dh = cfg.d_model, cfg.n_kv_heads, cfg.head_dim_
    H = cfg.padded_heads
    pdt = cfg.param_dtype
    kv_src = d  # memory is projected to d_model before blocks; keep uniform
    q_init, o_init = fan_in_init(0), fan_in_init(1)
    if H != cfg.n_heads:
        q_init = _head_padded_init(q_init, cfg, -2)  # (..., d, H, Dh)
        o_init = _head_padded_init(o_init, cfg, -3)  # (..., H, Dh, d)
    defs = {
        "wq": ParamDef((d, H, Dh), ("embed_fsdp", "heads", "head_dim"),
                       q_init, _dt(pdt)),
        "wk": ParamDef((kv_src, KV, Dh), ("embed_fsdp", "kv_heads", "head_dim"),
                       fan_in_init(0), _dt(pdt)),
        "wv": ParamDef((kv_src, KV, Dh), ("embed_fsdp", "kv_heads", "head_dim"),
                       fan_in_init(0), _dt(pdt)),
        "wo": ParamDef((H, Dh, d), ("heads", "head_dim", "embed_fsdp"),
                       o_init, _dt(pdt)),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((H, Dh), ("heads", "head_dim"), zeros_init(), _dt(pdt))
        defs["bk"] = ParamDef((KV, Dh), ("kv_heads", "head_dim"), zeros_init(), _dt(pdt))
        defs["bv"] = ParamDef((KV, Dh), ("kv_heads", "head_dim"), zeros_init(), _dt(pdt))
    return defs


def _dt(name: str):
    from repro.models.common import dtype_of

    return dtype_of(name)


def init_attn_cache_defs(
    cfg: ModelConfig, batch: int, max_seq: int, window: int = 0
) -> Dict[str, ParamDef]:
    """KV-cache buffer shapes for one attention block (ring buffer for SWA)."""
    KV, Dh = cfg.n_kv_heads, cfg.head_dim_
    S = min(window, max_seq) if window else max_seq
    return {
        "k": ParamDef((batch, S, KV, Dh), ("batch", "kv_seq", "kv_heads", "head_dim"),
                      zeros_init(), _dt(cfg.compute_dtype)),
        "v": ParamDef((batch, S, KV, Dh), ("batch", "kv_seq", "kv_heads", "head_dim"),
                      zeros_init(), _dt(cfg.compute_dtype)),
    }


# ---------------------------------------------------------------------------
# inner attention
# ---------------------------------------------------------------------------
def _gqa_logits(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B,Sq,H,D), k: (B,Sk,KV,D) -> logits (B,H,Sq,Sk) without
    materializing repeated KV heads."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32))
    return logits.reshape(B, KV * G, Sq, k.shape[1])


def _gqa_out(weights: jax.Array, v: jax.Array) -> jax.Array:
    """weights: (B,H,Sq,Sk), v: (B,Sk,KV,D) -> (B,Sq,H,D)."""
    B, H, Sq, Sk = weights.shape
    KV = v.shape[2]
    G = H // KV
    wg = weights.reshape(B, KV, G, Sq, Sk)
    out = jnp.einsum("bkgqs,bskd->bqkgd", wg, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1])


def _dense_attend(
    q, k, v, *, causal: bool, window: int, q_offset, kv_len: Optional[jax.Array],
) -> jax.Array:
    """``q_offset`` and ``kv_len`` may be scalars (the classic paths) or
    (B,)-vectors — the continuous-batching engine decodes slots sitting at
    *different* cache lengths in one dispatch.  The mask is built in a
    (B-or-1, Sq, Sk) frame so both shapes share one code path."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    logits = _gqa_logits(q, k) / math.sqrt(D)
    qoff = jnp.asarray(q_offset)
    qpos = qoff.reshape(-1, 1, 1) + jnp.arange(Sq)[None, :, None]
    kpos = jnp.arange(Sk)[None, None, :]
    mask = jnp.ones((1, Sq, Sk), bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window:
        mask = mask & (kpos > qpos - window)
    if kv_len is not None:
        mask = mask & (kpos < jnp.asarray(kv_len).reshape(-1, 1, 1))
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return _gqa_out(weights, v).astype(v.dtype)


def _blocked_attend(q, k, v, *, causal: bool, block_q: int, block_kv: int,
                    q_offset=0) -> jax.Array:
    """Flash-style two-level scan: memory O(block_q × block_kv)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    KV = k.shape[2]
    G = H // KV
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_kv
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_kv
    qb = qp.reshape(B, nq, block_q, KV, G, D).astype(jnp.float32) / math.sqrt(D)
    kb = kp.reshape(B, nk, block_kv, KV, D).astype(jnp.float32)
    vb = vp.reshape(B, nk, block_kv, KV, D).astype(jnp.float32)

    qpos = (q_offset + jnp.arange(nq * block_q)).reshape(nq, block_q)
    kpos = jnp.arange(nk * block_kv).reshape(nk, block_kv)
    kvalid = (jnp.arange(nk * block_kv) < Sk).reshape(nk, block_kv)

    def q_block(carry, qi):
        qblk, qp_blk = qi  # (B, bq, KV, G, D), (bq,)

        def kv_block(state, ki):
            m, l, acc = state
            kblk, vblk, kp_blk, kval = ki
            logits = jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk)
            mask = kval[None, :]
            if causal:
                mask = mask & (kp_blk[None, :] <= qp_blk[:, None])
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            blk_max = logits.max(-1)
            new_m = jnp.maximum(m, blk_max)
            scale = jnp.exp(m - new_m)
            p = jnp.exp(logits - new_m[..., None])
            new_l = l * scale + p.sum(-1)
            new_acc = acc * scale[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vblk
            )
            return (new_m, new_l, new_acc), None

        m0 = jnp.full((B, KV, G, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, KV, G, block_q, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0), (kb.swapaxes(0, 1), vb.swapaxes(0, 1),
                                     kpos, kvalid)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,KV,G,bq,D)
        return carry, out.transpose(0, 3, 1, 2, 4)  # (B,bq,KV,G,D)

    _, outs = jax.lax.scan(q_block, None, (qb.swapaxes(0, 1), qpos))
    # outs: (nq, B, bq, KV, G, D)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * block_q, H, D)
    return out[:, :Sq].astype(v.dtype)


def _local_attend(q, k, v, *, window: int, q_offset=0) -> jax.Array:
    """Banded attention: chunk size W; each Q chunk sees [prev|own] chunks.
    Exact for causal sliding-window of width ≤ W."""
    B, Sq, H, D = q.shape
    W = window
    pad = (-Sq) % W
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    S = qp.shape[1]
    nc = S // W
    KV = k.shape[2]
    G = H // KV
    qc = qp.reshape(B, nc, W, KV, G, D).astype(jnp.float32) / math.sqrt(D)
    kc = kp.reshape(B, nc, W, KV, D)
    vc = vp.reshape(B, nc, W, KV, D)
    # previous chunk (zeros for the first)
    kprev = jnp.pad(kc[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    vprev = jnp.pad(vc[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    k2 = jnp.concatenate([kprev, kc], axis=2).astype(jnp.float32)  # (B,nc,2W,KV,D)
    v2 = jnp.concatenate([vprev, vc], axis=2).astype(jnp.float32)
    logits = jnp.einsum("bnqkgd,bnskd->bnkgqs", qc, k2)
    qpos = jnp.arange(W)[:, None] + W  # position within the 2W window frame
    kpos = jnp.arange(2 * W)[None, :]
    mask = (kpos <= qpos) & (kpos > qpos - window)
    # first chunk: "previous" half is padding
    first_mask = mask & (kpos >= W)
    chunk_idx = jnp.arange(nc)
    full_mask = jnp.where((chunk_idx == 0)[:, None, None], first_mask[None],
                          mask[None])  # (nc, W, 2W)
    # global padding validity on kv side
    kvalid = jnp.concatenate(
        [jnp.pad((jnp.arange(S) < Sq).reshape(nc, W)[:-1], ((1, 0), (0, 0))),
         (jnp.arange(S) < Sq).reshape(nc, W)], axis=1)  # (nc, 2W)
    full_mask = full_mask & kvalid[:, None, :]
    logits = jnp.where(full_mask[None, :, None, None], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnkgqs,bnskd->bnqkgd", weights, v2)
    out = out.reshape(B, S, H, D)[:, :Sq]
    return out.astype(v.dtype)


def attend(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    cfg: ModelConfig,
    causal: bool = True,
    window: int = 0,
    impl: Optional[str] = None,
    q_offset: Any = 0,
    kv_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Dispatch to an inner attention implementation."""
    impl = impl or cfg.attn_impl
    Sq, Sk = q.shape[1], k.shape[1]
    if window and causal and Sq == Sk and Sk <= window:
        window = 0  # the window covers the whole causal context: no-op
    if impl == "auto":
        if Sq == 1 or kv_len is not None:
            impl = "dense"  # decode: one query row, einsum over the cache
        elif window and causal and Sq == Sk and Sk > 2 * window:
            impl = "local"
        elif Sk >= 2 * cfg.attn_block_kv:
            impl = "blocked"
        else:
            impl = "dense"
    if impl == "pallas":
        from repro.kernels.ops import flash_attention as pallas_flash

        return pallas_flash(q, k, v, causal=causal, window=window)
    if impl == "local":
        return _local_attend(q, k, v, window=window, q_offset=q_offset)
    if impl == "blocked":
        out = _blocked_attend(
            q, k, v, causal=causal, block_q=cfg.attn_block_q,
            block_kv=cfg.attn_block_kv, q_offset=q_offset,
        )
        if window:  # blocked path is exact only without a window; guard
            raise ValueError("blocked impl does not support sliding window")
        return out
    if impl == "dense":
        return _dense_attend(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, kv_len=kv_len)
    raise ValueError(f"unknown attention impl {impl!r}")


# ---------------------------------------------------------------------------
# block-level wrappers
# ---------------------------------------------------------------------------
def _mask_padded_heads(y: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Zero the attention output of padded heads: blocks gradient flow into
    the zero-initialized pad weights, making head padding training-exact."""
    if cfg.padded_heads == cfg.n_heads:
        return y
    valid = _valid_head_mask(cfg)
    return y * valid[None, None, :, None].astype(y.dtype)


def _project_qkv(params, x, memory, cfg: ModelConfig):
    cdt = _dt(cfg.compute_dtype)
    src = x.astype(cdt)
    mem = (memory if memory is not None else x).astype(cdt)
    q = jnp.einsum("bsd,dhk->bshk", src, params["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", mem, params["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", mem, params["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(cdt)
        k = k + params["bk"].astype(cdt)
        v = v + params["bv"].astype(cdt)
    return q, k, v


def self_attention(
    params: Dict[str, jax.Array],
    x: jax.Array,
    *,
    cfg: ModelConfig,
    positions: jax.Array,  # (B, S) absolute positions of x tokens
    window: int = 0,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_index: Optional[jax.Array] = None,  # scalar or (B,): cached tokens
    impl: Optional[str] = None,
    causal: bool = True,
    page_table: Optional[jax.Array] = None,  # (B, MAXG): paged KV layout
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Causal self-attention; updates the KV cache when one is given.

    Without a cache: full-sequence training/prefill-style attention.
    With a cache: ``x`` holds new token(s); K/V are appended (ring-buffer
    writes for sliding-window blocks) and attention runs against the buffer.
    A (B,)-vector ``cache_index`` is the continuous-batching decode path:
    every slot appends its single token at its *own* position.  With
    ``page_table`` the cache is a (groups, KV, group_tokens, D) pool and
    the append/attend go through the table (``repro.serve.paging``).
    """
    q, k, v = _project_qkv(params, x, None, cfg)
    cos, sin = rope_freqs(positions, cfg.head_dim_, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")

    new_cache = None
    if cache is not None and page_table is not None:
        B = x.shape[0]
        T = cache["k"].shape[2]
        S_new = k.shape[1]
        pos = jnp.asarray(cache_index, jnp.int32).reshape(B)
        if S_new == 1:
            # Paged pool: single-token decode append through the table.
            gid = page_table[jnp.arange(B), pos // T]
            off = pos % T
            ck = cache["k"].at[gid, :, off].set(
                k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[gid, :, off].set(
                v[:, 0].astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv}
            y = _paged_decode_attend(q, ck, cv, page_table, pos + 1)
        else:
            # Speculative verify: C tokens per slot, positions
            # pos..pos+C-1.  Columns past the page table (a draft chain
            # overrunning max_seq on a request that will finish first)
            # are routed out of range and dropped by the scatter; columns
            # past a slot's reservation land in the scratch entries of
            # its table row — either way they are masked KV no valid
            # query ever reads, so the accepted prefix stays exact.
            G_pool = cache["k"].shape[0]
            MAXG = page_table.shape[1]
            ppos = pos[:, None] + jnp.arange(S_new, dtype=jnp.int32)
            lg = ppos // T
            gid = jnp.where(
                lg < MAXG,
                page_table[jnp.arange(B)[:, None],
                           jnp.minimum(lg, MAXG - 1)],
                G_pool)
            off = ppos % T
            ck = cache["k"].at[gid, :, off].set(
                k.astype(cache["k"].dtype), mode="drop")
            cv = cache["v"].at[gid, :, off].set(
                v.astype(cache["v"].dtype), mode="drop")
            new_cache = {"k": ck, "v": cv}
            y = _paged_verify_attend(q, ck, cv, page_table, pos)
    elif cache is not None:
        Sbuf = cache["k"].shape[1]
        S_new = k.shape[1]
        if jnp.ndim(cache_index) == 1:
            # Continuous batching: each slot appends token(s) at its own
            # cache length (scatter write; per-slot masks in the attend).
            # S_new > 1 is the speculative-verify chain — the dense mask
            # already handles vector q_offset with multi-token queries,
            # and writes past the buffer (overrunning draft columns) are
            # dropped rather than clamped onto live positions.
            B = x.shape[0]
            idx = cache_index.astype(jnp.int32)
            if S_new == 1:
                ck = cache["k"].at[jnp.arange(B), idx].set(
                    k[:, 0].astype(cache["k"].dtype))
                cv = cache["v"].at[jnp.arange(B), idx].set(
                    v[:, 0].astype(cache["v"].dtype))
            else:
                ppos = idx[:, None] + jnp.arange(S_new, dtype=jnp.int32)
                ck = cache["k"].at[jnp.arange(B)[:, None], ppos].set(
                    k.astype(cache["k"].dtype), mode="drop")
                cv = cache["v"].at[jnp.arange(B)[:, None], ppos].set(
                    v.astype(cache["v"].dtype), mode="drop")
            new_cache = {"k": ck, "v": cv}
            y = attend(q, ck, cv, cfg=cfg, causal=True, window=0,
                       impl="dense", kv_len=idx + S_new, q_offset=idx)
        else:
            if window and Sbuf == window:
                write_pos = (cache_index % window).astype(jnp.int32)
            else:
                write_pos = cache_index.astype(jnp.int32)
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, write_pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, write_pos, 0, 0))
            new_cache = {"k": ck, "v": cv}
            total = cache_index + S_new
            if window and Sbuf == window:
                # Ring buffer (sliding window): single-step decode writes.
                y = _ring_decode_attend(q, ck, cv, cache_index, window)
            else:
                # Causal over the buffer: new tokens sit at
                # q_offset=cache_index; only the first `total` slots valid.
                y = attend(q, ck, cv, cfg=cfg, causal=True, window=0,
                           impl="dense", kv_len=total, q_offset=cache_index)
    else:
        y = attend(q, k, v, cfg=cfg, causal=causal, window=window, impl=impl)

    y = _mask_padded_heads(y, cfg)
    y = constrain(y, "batch", "seq", "heads", "head_dim")
    cdt = _dt(cfg.compute_dtype)
    out = jnp.einsum("bshk,hkd->bsd", y.astype(cdt), params["wo"].astype(cdt))
    return constrain(out, "batch", "seq_res", "embed"), new_cache


def _paged_decode_attend(q, k_pages, v_pages, page_table, lengths):
    """Decode attention over a paged pool (single-step q).

    On accelerator backends this is the Pallas paged kernel (the page
    table rides in as a scalar-prefetch operand, so K/V stream straight
    from their physical groups); on CPU the pure-jnp gather reference —
    interpret-mode Pallas times the Python emulator, not the hardware,
    exactly like the other kernel entry points."""
    from repro.kernels.ops import default_interpret
    from repro.kernels.paged_attention import paged_attention_ref

    qs = q[:, 0]
    if default_interpret():
        out = paged_attention_ref(qs, k_pages, v_pages, page_table, lengths)
    else:
        out = _paged_kernel_per_shard(qs, k_pages, v_pages, page_table,
                                      lengths)
    return out[:, None].astype(v_pages.dtype)


def _paged_kernel_per_shard(q, k_pages, v_pages, page_table, lengths):
    """The Pallas paged kernel, run per shard under an active mesh.

    GSPMD cannot partition a Mosaic kernel, so under ``axis_rules`` with
    a mesh the call runs inside ``shard_map``: slots split over the
    batch rule's mesh axis, and KV heads (with their query-head groups,
    which stay contiguous per shard) over the axis the pool's ``kv_heads``
    dim is split on (``POOL_AXES``) — or not at all where it replicates.
    Without a mesh it is the plain kernel call."""
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import current_rules, spec_for_shape
    from repro.kernels.ops import paged_flash_decode
    from repro.kernels.paged_attention import POOL_AXES

    active = current_rules()
    if active is None or active[1] is None:
        return paged_flash_decode(q, k_pages, v_pages, page_table, lengths)
    rules, mesh = active
    pool = spec_for_shape(k_pages.shape, POOL_AXES, rules, mesh)
    heads = pool[1] if len(pool) > 1 else None
    batch = spec_for_shape(lengths.shape, ("batch",), rules, mesh)
    rows = batch[0] if len(batch) else None
    per_shard = jax.shard_map(
        paged_flash_decode, mesh=mesh,
        in_specs=(P(rows, heads), P(*pool), P(*pool), P(rows), P(rows)),
        out_specs=P(rows, heads), check_vma=False)
    return per_shard(q, k_pages, v_pages, page_table, lengths)


def _paged_verify_attend(q, k_pages, v_pages, page_table, base):
    """Multi-token decode attention over a paged pool (speculative verify).

    ``paged_attention_ref`` generalized to C query columns per slot:
    gather the pool into logical order through the page table, then
    masked attention where column i (absolute position ``base + i``)
    sees key positions ``< base + i + 1``.  Scratch-group and
    rejected-tail writes are masked out the same way stale pool tokens
    are in the single-token ref, so the accepted prefix attends exactly
    the KV a draft-free run would."""
    from repro.kernels.paged_attention import gather_pages

    B, C, H, D = q.shape
    k = gather_pages(k_pages, page_table).astype(jnp.float32)
    v = gather_pages(v_pages, page_table).astype(jnp.float32)
    KV = k.shape[2]
    Gq = H // KV
    qg = q.reshape(B, C, KV, Gq, D).astype(jnp.float32) / math.sqrt(D)
    s = jnp.einsum("bckgd,bskd->bkgcs", qg, k)
    kpos = jnp.arange(k.shape[1])[None, None, None, None, :]
    qpos = (base[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :])
    s = jnp.where(kpos <= qpos[:, None, None, :, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgcs,bskd->bckgd", w, v)
    return out.reshape(B, C, H, D).astype(v_pages.dtype)


def _ring_decode_attend(q, ck, cv, cache_index, window):
    """Decode attention over a ring-buffer SWA cache (single-step q)."""
    B, Sq, H, D = q.shape
    W = ck.shape[1]
    # slot s holds absolute position: valid if pos > cache_index - window
    slots = jnp.arange(W)
    # absolute position stored in slot s (when cache_index tokens written):
    # last write at (cache_index) -> slot cache_index % W.
    total = cache_index + Sq
    age = (jnp.int32(total - 1) - slots) % W  # 0 = newest ... W-1 oldest
    valid = age < jnp.minimum(total, W)
    logits = _gqa_logits(q, ck) / math.sqrt(D)
    logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return _gqa_out(weights, cv).astype(cv.dtype)


def cross_attention(
    params: Dict[str, jax.Array],
    x: jax.Array,
    memory: jax.Array,
    *,
    cfg: ModelConfig,
    memory_kv: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Cross-attention to a fixed memory (image patches / audio frames /
    encoder output).  ``memory_kv``: precomputed K/V for decode steps."""
    cdt = _dt(cfg.compute_dtype)
    if memory_kv is None:
        q, k, v = _project_qkv(params, x, memory, cfg)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x.astype(cdt),
                       params["wq"].astype(cdt))
        if cfg.qkv_bias:
            q = q + params["bq"].astype(cdt)
        k, v = memory_kv["k"], memory_kv["v"]
    y = _mask_padded_heads(attend(q, k, v, cfg=cfg, causal=False, window=0,
                                  impl="dense"), cfg)
    out = jnp.einsum("bshk,hkd->bsd", y.astype(cdt), params["wo"].astype(cdt))
    kv = {"k": k, "v": v} if memory_kv is None else memory_kv
    return constrain(out, "batch", "seq", "embed"), kv
