"""Paged flash-decode Pallas kernel: gather K/V through a page table.

The continuous-batching engine stores KV cache in a pool of fixed-size
page *groups* (``repro.serve.paging``); a request's tokens live in
whatever groups the allocator handed it, in logical order given by its
page-table row.  Dense decode attention would first gather the pool into
a contiguous per-slot buffer — an extra O(B·S) HBM round trip per step.
This kernel streams the pool *directly*: the page table rides in as a
scalar-prefetch operand, so each grid step's K/V block is DMA'd straight
from its physical group (``index_map`` reads the page table — the Pallas
TPU idiom for data-dependent addressing).

Layout: the pool is head-major, (groups, KV-head, T, D), so one page
group of one KV head is a contiguous (T, D) slab — the kernel's block.
Its last two dims are T (a multiple of ``PAGE_TOKENS``) and the full
head_dim, which is what Mosaic's (8, 128) block rule accepts at every
head_dim.  Grid (B, KV-head, logical-groups); all G query heads of a KV
group are processed together as a (G, D) tile (decode_attention's GQA
bandwidth win, unchanged).  Online-softmax state lives in 2-D VMEM
scratch across the group dimension; groups past a sequence's valid
length are skipped with ``pl.when`` — a 2k-token request in a
32k-capacity pool streams 2k tokens, and *only its own* pages.

``pages_per_block`` is structural here: the pool's token axis is
``pages_per_block * PAGE_TOKENS`` tokens, so the tuning knob is applied
where the pool is laid out (engine/allocator) and this kernel simply
tiles one group per grid step.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["POOL_AXES", "gather_pages", "paged_flash_decode_pallas",
           "paged_attention_ref", "shardable_kv_heads"]

NEG_INF = -1e30

# Logical sharding axes of the (G, KV, T, D) KV pool this kernel streams.
# The group and token axes are deliberately unsharded: the grid tiles ONE
# physical group per step through the scalar-prefetched page table, so a
# split along groups would scatter a request's logically-contiguous pages
# across devices and break the ``index_map`` addressing.  Only the KV-head
# axis splits (tensor parallelism): each model-axis shard streams its own
# heads over the full pool, and the kernel's (pages_per_block x
# PAGE_TOKENS) group tile stays aligned with the allocator's group size on
# every shard.  ``repro.models.transformer.paged_cache_block_defs`` builds
# pool ParamDefs from this tuple — one source for the kernel/allocator/
# sharding coupling.
POOL_AXES = (None, "kv_heads", None, "head_dim")


def shardable_kv_heads(n_kv_heads: int, model_size: int) -> bool:
    """Whether a ``model_size``-way TP split actually shards the KV pool.

    Mirrors ``spec_for_shape``'s divisibility fallback for the pool's
    ``kv_heads`` axis: when ``n_kv_heads % model_size != 0`` the pool is
    silently *replicated* per device instead — deployable (the kernel
    sees the full head set on every shard) but without the memory win,
    which is why ``serve_feasibility`` surfaces it as a warn-severity
    advisory rather than hard infeasibility.
    """
    m = max(1, int(model_size))
    return m == 1 or int(n_kv_heads) % m == 0


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, group_tokens: int, scale: float):
    b = pl.program_id(0)
    g = pl.program_id(2)
    ng = pl.num_programs(2)
    length = len_ref[b]

    @pl.when(g == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = g * group_tokens

    @pl.when(base < length)  # skip groups past the valid length
    def compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (T, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (T, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_ref[...]  # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(g == ng - 1)
    def finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_flash_decode_pallas(
    q: jax.Array,           # (B, H, D) — one new token per sequence
    k_pages: jax.Array,     # (G, KV, T, D) pool; T tokens per group
    v_pages: jax.Array,     # (G, KV, T, D)
    page_table: jax.Array,  # (B, MAXG) int32: logical group -> physical
    lengths: jax.Array,     # (B,) int32: valid tokens per sequence
    *,
    dimension_semantics: Optional[str] = None,  # None|'arbitrary'|'parallel'
    num_warps: Optional[int] = None,  # GPU-lowering hint; inert on TPU
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    G_pool, KV, T, _ = k_pages.shape
    MAXG = page_table.shape[1]
    if H % KV:
        raise ValueError(f"H={H} not a multiple of KV={KV}")
    Gq = H // KV
    qg = q.reshape(B, KV, Gq, D)
    page_table = jnp.asarray(page_table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)

    # The batch and head grid dims are embarrassingly parallel; the group
    # dim carries the online-softmax scratch and must stay "arbitrary".
    # num_warps is accepted for signature parity with the GPU lowering
    # (where it would reach the Triton compiler); Mosaic has no analog.
    from .launch import launch_params

    params = launch_params(dimension_semantics, 3, 1, interpret)
    del num_warps

    kwargs = {"compiler_params": params} if params else {}
    out = pl.pallas_call(
        functools.partial(_kernel, group_tokens=T,
                          scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # page_table, lengths
            grid=(B, KV, MAXG),
            in_specs=[
                pl.BlockSpec((1, 1, Gq, D),
                             lambda b, h, g, pt, ln: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, T, D),
                             lambda b, h, g, pt, ln: (pt[b, g], h, 0, 0)),
                pl.BlockSpec((1, 1, T, D),
                             lambda b, h, g, pt, ln: (pt[b, g], h, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, Gq, D),
                                   lambda b, h, g, pt, ln: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Gq, 1), jnp.float32),
                pltpu.VMEM((Gq, 1), jnp.float32),
                pltpu.VMEM((Gq, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, Gq, D), q.dtype),
        interpret=interpret,
        **kwargs,
    )(page_table, lengths, qg, k_pages, v_pages)
    return out.reshape(B, H, D)


def gather_pages(pages, page_table):
    """(G, KV, T, D) pool -> (B, MAXG*T, KV, D) logical per-slot caches:
    each slot's groups gathered through its page-table row, in order."""
    g = pages[page_table]  # (B, MAXG, KV, T, D)
    B, maxg, KV, T, D = g.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(B, maxg * T, KV, D)


def paged_attention_ref(q, k_pages, v_pages, page_table, lengths):
    """Pure-jnp oracle: gather the pool into logical order, then masked
    attention.  Also the CPU execution path of the paged serve engine
    (interpret-mode Pallas times the Python emulator, not the TPU)."""
    B, H, D = q.shape
    k = gather_pages(k_pages, page_table).astype(jnp.float32)
    v = gather_pages(v_pages, page_table).astype(jnp.float32)
    KV = k.shape[2]
    Gq = H // KV
    qg = q.reshape(B, KV, Gq, D).astype(jnp.float32) / math.sqrt(D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k)
    kpos = jnp.arange(k.shape[1])
    s = jnp.where(kpos[None, None, None, :] < lengths[:, None, None, None],
                  s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v)
    return out.reshape(B, H, D).astype(q.dtype)
