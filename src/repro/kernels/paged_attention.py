"""Paged flash-decode Pallas kernel: gather K/V through a page table.

The continuous-batching engine stores KV cache in a pool of fixed-size
page *groups* (``repro.serve.paging``); a request's tokens live in
whatever groups the allocator handed it, in logical order given by its
page-table row.  Dense decode attention would first gather the pool into
a contiguous per-slot buffer — an extra O(B·S) HBM round trip per step.
This kernel streams the pool *directly*: the page table and the lengths
ride in as scalar-prefetch operands, and the kernel copies each valid
group from its physical place in HBM into VMEM itself.

Layout: the pool is (groups, KV-head, T, D), so one page group with all
its KV heads is one contiguous (KV, T, D) slab.  Grid (B, blocks): a
grid step takes ``n`` whole groups of one slot (``block_groups``: about
1 MiB of K, 8 groups at Qwen2.5-32B widths), one DMA of K and one of V
per group, into a VMEM double buffer; it starts the next step's copies
before it computes on its own.  Groups at or past a slot's length get no
copy, and blocks past it no work, so a 2k-token request in a
32k-capacity pool streams 2k tokens, and *only its own* pages.  The
query and output blocks are one slot's (KV, Gq, D).  Scores and P·V of
a step's n groups and all heads are two batched f32 matmuls; the f32
online softmax (state in (KV, Gq, ·) VMEM scratch) then advances one
group at a time, so the output is bit-identical to a grid of one group
a step.

``pages_per_block`` is structural here: the pool's token axis is
``pages_per_block * PAGE_TOKENS`` tokens, so the tuning knob is applied
where the pool is laid out (engine/allocator); the kernel picks ``n``
from the shape it sees.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.serve.paging import block_groups

__all__ = ["POOL_AXES", "gather_pages", "paged_flash_decode_pallas",
           "paged_attention_ref", "shardable_kv_heads"]

NEG_INF = -1e30

# Logical sharding axes of the (G, KV, T, D) KV pool this kernel streams.
# The group and token axes are deliberately unsharded: the kernel copies
# whole physical groups through the scalar-prefetched page table, so a
# split along groups would scatter a request's logically-contiguous pages
# across devices and break that addressing.  Only the KV-head axis splits
# (tensor parallelism): each model-axis shard streams its own heads over
# the full pool, and the kernel's (pages_per_block x PAGE_TOKENS) group
# tile stays aligned with the allocator's group size on every shard.
# ``repro.models.transformer.paged_cache_block_defs`` builds pool
# ParamDefs from this tuple — one source for the kernel/allocator/
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


def _kernel(pt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
            sems, m_ref, l_ref, acc_ref, *, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    _, n, KV, T, D = k_buf.shape
    maxg = pt_ref.shape[1]
    length = len_ref[b]
    n_groups = jnp.minimum((length + T - 1) // T, maxg)
    n_blocks = (n_groups + n - 1) // n
    slot = j % 2

    def each_group(blk, buf_slot, fn):
        # one (KV, T, D) slab of K and of V per valid group of block blk;
        # no copy for groups at or past the slot's length
        for i in range(n):
            g = blk * n + i

            @pl.when(g < n_groups)
            def _():
                page = pt_ref[b, g]
                fn(pltpu.make_async_copy(k_hbm.at[page],
                                         k_buf.at[buf_slot, i],
                                         sems.at[0, buf_slot]))
                fn(pltpu.make_async_copy(v_hbm.at[page],
                                         v_buf.at[buf_slot, i],
                                         sems.at[1, buf_slot]))

    @pl.when(j == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(n_blocks > 0)
        def first_fetch():
            each_group(0, 0, lambda c: c.start())

    @pl.when(j < n_blocks)  # blocks past the valid length do nothing
    def compute():
        @pl.when(j + 1 < n_blocks)
        def prefetch():
            each_group(j + 1, 1 - slot, lambda c: c.start())

        each_group(j, slot, lambda c: c.wait())
        # Scores and P·V for all n groups and KV heads come from two
        # batched matmuls; the online softmax then steps group by group,
        # as a grid of one group a step would, and gives its bits.
        Gq = q_ref.shape[2]
        q = q_ref[0].astype(jnp.float32) * scale  # (KV, Gq, D)
        qb = jnp.broadcast_to(q[None], (n, KV, Gq, D)).reshape(
            n * KV, Gq, D)
        grp = j * n + jax.lax.broadcasted_iota(jnp.int32, (n, 1, 1, 1), 0)
        k = k_buf[slot].astype(jnp.float32).reshape(n * KV, T, D)
        # groups past the length hold stale VMEM: zero V there so that
        # 0 * garbage cannot reach the accumulator
        v = jnp.where(grp < n_groups, v_buf[slot].astype(jnp.float32), 0.0)
        s = jax.lax.dot_general(qb, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        s = s.reshape(n, KV, Gq, T)
        kpos = grp * T + jax.lax.broadcasted_iota(jnp.int32, (n, 1, 1, T), 3)
        s = jnp.where(kpos < length, s, NEG_INF)
        ms = [m_ref[...]]  # running max after each group, (KV, Gq, 1)
        for i in range(n):
            ms.append(jnp.maximum(ms[-1], s[i].max(axis=2, keepdims=True)))
        p = jnp.exp(s - jnp.stack(ms[1:]))
        pv = jax.lax.dot_general(p.reshape(n * KV, Gq, T),
                                 v.reshape(n * KV, T, D),
                                 (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        pv = pv.reshape(n, KV, Gq, D)
        # a group past the length is a no-op here: alpha 1, p and P·V 0
        l, acc = l_ref[...], acc_ref[...]
        for i in range(n):
            alpha = jnp.exp(ms[i] - ms[i + 1])
            l = l * alpha + p[i].sum(axis=2, keepdims=True)
            acc = acc * alpha + pv[i]
        m_ref[...], l_ref[...], acc_ref[...] = ms[n], l, acc

    @pl.when(j == nj - 1)
    def finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


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
    n = block_groups(KV, T, D, k_pages.dtype.itemsize, MAXG)
    qg = q.reshape(B, KV, Gq, D)
    page_table = jnp.asarray(page_table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)

    # Each slot's copies start and end inside its own grid steps, so the
    # batch dim is embarrassingly parallel; the block dim carries the
    # online-softmax scratch and the double buffer and stays "arbitrary".
    # num_warps is accepted for signature parity with the GPU lowering
    # (where it would reach the Triton compiler); Mosaic has no analog.
    from .launch import launch_params

    params = launch_params(dimension_semantics, 2, 1, interpret)
    del num_warps

    kwargs = {"compiler_params": params} if params else {}
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # page_table, lengths
            grid=(B, pl.cdiv(MAXG, n)),
            in_specs=[
                pl.BlockSpec((1, KV, Gq, D),
                             lambda b, j, pt, ln: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, KV, Gq, D),
                                   lambda b, j, pt, ln: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, n, KV, T, D), k_pages.dtype),
                pltpu.VMEM((2, n, KV, T, D), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),  # (K|V, buffer)
                pltpu.VMEM((KV, Gq, 1), jnp.float32),
                pltpu.VMEM((KV, Gq, 1), jnp.float32),
                pltpu.VMEM((KV, Gq, D), jnp.float32),
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
