"""Kernel block-configuration spaces + analytic cost models.

``KernelSpace`` turns each Pallas kernel's tiling knobs into an ACTS
``ParameterSpace`` so the ordinary tuner stack (LHS + RRS, budget, cache,
report) drives kernel autotuning exactly like it drives MySQL knobs — the
paper's architecture pointed at our own hot path.

Per kernel: the knob space, an input builder for a problem signature, a
call adapter, and a roofline-style cost model.  The model is the CPU-side
stand-in for wall-clock timing (interpret-mode timings are meaningless for
TPU performance): it scores a block config by grid-step overhead + per-tile
MXU/VPU time + HBM streaming, with hard VMEM-capacity infeasibility and
sublane/lane alignment penalties (TPU tiles are (8/16/32, 128) — see the
Pallas guide).  On real TPU hardware the ``time`` mode measures instead.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core.params import EnumParam, ParameterSpace

__all__ = ["KernelSpace", "KERNELS", "shape_sig"]

VMEM_BYTES = 16 * 2**20  # per-core VMEM (v5e-class)
MXU_FLOPS_PER_S = 394e12 * 0.5  # bf16 peak derated
HBM_BYTES_PER_S = 819e9
GRID_STEP_OVERHEAD_S = 1.5e-6  # per grid step (dispatch + DMA setup)


def shape_sig(dims: Dict[str, int]) -> str:
    """Canonical problem signature, e.g. ``B2_D64_H4_KV2_S256``."""
    return "_".join(f"{k}{int(v)}" for k, v in sorted(dims.items()))


def _dtype_bytes(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}[dtype]


def _sublane(dtype: str) -> int:
    return {"float32": 8, "bfloat16": 16, "float16": 16}[dtype]


def _align_penalty(block: int, dtype: str) -> float:
    """Mosaic pads tiles to (sublane, 128); fractional-tile waste factor."""
    sub = _sublane(dtype)
    padded = math.ceil(block / sub) * sub
    return padded / max(block, 1)


def _dispatch_s(config: Dict[str, Any], n_steps: float,
                tile_elems: float) -> float:
    """Grid-scheduling time under the shared launch knobs.

    ``dim_semantics``: marking the non-reduction grid dims "parallel"
    lets Mosaic split them across the two TPU cores (megacore), halving
    the serialized step count.  ``num_warps`` is the GPU-lowering
    occupancy hint: more warps amortize per-step dispatch ~sqrt(n) but
    pay linear scheduling overhead, and a tile too small to feed them
    (``tile_elems``) caps the effective count — so the optimum co-moves
    with the block-size knobs instead of saturating at the rail.
    """
    steps = n_steps
    if config.get("dim_semantics", "arbitrary") == "parallel":
        steps = n_steps / 2.0
    nw = int(config.get("num_warps", 4))
    eff = min(nw, max(1.0, tile_elems / 2048.0))
    per_step = GRID_STEP_OVERHEAD_S * (1.0 + 0.08 * nw) / math.sqrt(eff)
    return steps * per_step


def _roofline_s(flops: float, hbm_bytes: float, n_steps: float,
                vmem_bytes: float, config: Optional[Dict[str, Any]] = None,
                tile_elems: float = 0.0) -> float:
    if vmem_bytes > VMEM_BYTES:
        return math.inf  # tile set does not fit on-chip
    compute = flops / MXU_FLOPS_PER_S
    stream = hbm_bytes / HBM_BYTES_PER_S
    if config is None:
        dispatch = n_steps * GRID_STEP_OVERHEAD_S
    else:
        dispatch = _dispatch_s(config, n_steps, tile_elems)
    return max(compute, stream) + dispatch


# ---------------------------------------------------------------------------
# per-kernel definitions
# ---------------------------------------------------------------------------
_POW2_BLOCKS = (16, 32, 64, 128, 256, 512)

# Shared launch knobs (ROADMAP PR-1 open item): every kernel space carries
# the Mosaic grid dimension-semantics choice, threaded to every kernel's
# ``pltpu.CompilerParams`` and through the cost model's ``_dispatch_s``
# term, so ACTS tunes it jointly with the block sizes.  The GPU num_warps
# occupancy hint is *plumbed* (every kernel and ``_dispatch_s`` accept
# it) but joins a tune space only on backends whose lowering consumes it
# — none today: on TPU it is inert, and an inert axis in ``mode="time"``
# would spend wall-clock budget re-measuring identical kernels.
def _with_launch_knobs(params: list, warps: bool = False) -> ParameterSpace:
    params = params + [EnumParam("dim_semantics",
                                 ("arbitrary", "parallel"), "parallel")]
    if warps:
        params.append(EnumParam("num_warps", (2, 4, 8), 4))
    return ParameterSpace(params)


@dataclass(frozen=True)
class KernelDef:
    name: str
    dims: Tuple[str, ...]  # required signature dims
    knobs: Tuple[str, ...]
    make_space: Callable[[], ParameterSpace]
    make_inputs: Callable[[Dict[str, int], str, np.random.Generator], tuple]
    call: Callable[[tuple, Dict[str, Any], bool], Any]
    model_cost: Callable[[Dict[str, Any], Dict[str, int], str], float]
    # (config, dims, dtype) -> VMEM bytes of the kernel's resident tile
    # set.  The SINGLE source of the cost model's hard infeasibility
    # (``_roofline_s`` returns inf iff this exceeds ``VMEM_BYTES``) and of
    # the static feasibility predicate (``repro.analysis.feasibility``) —
    # sharing the function is what keeps ``feasible(cfg) ⇔ cost < inf``
    # exact instead of a re-derivation that drifts.
    vmem_footprint: Callable[[Dict[str, Any], Dict[str, int], str], float]


def _rand(rng, shape, dtype):
    import jax.numpy as jnp

    return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(
        {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
         "float16": jnp.float16}[dtype])


# -- flash attention ---------------------------------------------------------
def _fa_space() -> ParameterSpace:
    return _with_launch_knobs([
        EnumParam("block_q", _POW2_BLOCKS, 128),
        EnumParam("block_kv", _POW2_BLOCKS, 128),
    ])


def _fa_inputs(d, dtype, rng):
    q = _rand(rng, (d["B"], d["S"], d["H"], d["D"]), dtype)
    k = _rand(rng, (d["B"], d["SK"], d["KV"], d["D"]), dtype)
    v = _rand(rng, (d["B"], d["SK"], d["KV"], d["D"]), dtype)
    return q, k, v


def _launch_kw(config) -> Dict[str, Any]:
    """The shared launch knobs, passed through to every kernel call so
    ``mode="time"`` wall-clocks what the knobs actually change on TPU."""
    return {"dimension_semantics": config.get("dim_semantics"),
            "num_warps": config.get("num_warps")}


def _fa_call(inputs, config, interpret):
    from repro.kernels.flash_attention import flash_attention_pallas

    q, k, v = inputs
    # SK >= S: queries sit at the end of the KV stream (cache-prefill
    # semantics).  SK < S is encoder-decoder cross-attention — no causal
    # structure exists there, so time it unmasked rather than handing the
    # kernel a negative offset.
    causal = k.shape[1] >= q.shape[1]
    q_offset = k.shape[1] - q.shape[1] if causal else 0
    return flash_attention_pallas(q, k, v, causal=causal,
                                  q_offset=q_offset,
                                  block_q=config["block_q"],
                                  block_kv=config["block_kv"],
                                  interpret=interpret,
                                  **_launch_kw(config))


def _fa_vmem(config, d, dtype):
    """Resident tiles: q + double-buffered k/v blocks + f32 m/l/acc rows."""
    bq = min(config["block_q"], d["S"])
    bk = min(config["block_kv"], d["SK"])
    ib = _dtype_bytes(dtype)
    return (bq * d["D"] + 2 * bk * d["D"]) * ib + bq * (2 + d["D"]) * 4


def _fa_cost(config, d, dtype):
    B, S, SK, H, D = d["B"], d["S"], d["SK"], d["H"], d["D"]
    bq = min(config["block_q"], S)
    bk = min(config["block_kv"], SK)
    nq, nk = math.ceil(S / bq), math.ceil(SK / bk)
    n_steps = B * H * nq * nk
    # causal reachability: with the queries at the end of the KV stream
    # (q_offset = SK - S), row i of S sees SK - S + i + 1 keys; averaging
    # gives the live tile-pair fraction below (0.55 at SK == S, -> 1 as the
    # cached prefix dominates).  SK < S is cross-attention: unmasked, so
    # every tile pair is live (matches _fa_call's causal choice).
    frac = max(0.15, 1.0 - 0.45 * S / SK) if SK >= S else 1.0
    live = frac * n_steps
    pad = _align_penalty(bq, dtype) * _align_penalty(bk, dtype)
    flops = live * (4.0 * bq * bk * D) * pad
    ib = _dtype_bytes(dtype)
    hbm = (B * H * nq * bq * D * ib          # q tiles
           + 2.0 * live * bk * D * ib        # streamed k/v tiles
           + B * H * S * D * ib)             # output (S query rows)
    vmem = _fa_vmem(config, d, dtype)
    return _roofline_s(flops, hbm, n_steps, vmem, config, bq * bk)


# -- decode attention --------------------------------------------------------
def _fd_space() -> ParameterSpace:
    return _with_launch_knobs([
        EnumParam("block_kv", (32, 64, 128, 256, 512, 1024), 256),
    ])


def _fd_inputs(d, dtype, rng):
    q = _rand(rng, (d["B"], d["H"], d["D"]), dtype)
    k = _rand(rng, (d["B"], d["S"], d["KV"], d["D"]), dtype)
    v = _rand(rng, (d["B"], d["S"], d["KV"], d["D"]), dtype)
    return q, k, v, d["S"]


def _fd_call(inputs, config, interpret):
    from repro.kernels.decode_attention import flash_decode_pallas

    q, k, v, kv_len = inputs
    return flash_decode_pallas(q, k, v, kv_len,
                               block_kv=config["block_kv"],
                               interpret=interpret,
                               **_launch_kw(config))


def _fd_vmem(config, d, dtype):
    """Resident tiles: k/v blocks + per-group f32 m/l/acc + query group."""
    G = max(d["H"] // d["KV"], 1)
    bk = min(config["block_kv"], d["S"])
    ib = _dtype_bytes(dtype)
    return 2 * bk * d["D"] * ib + G * (2 + d["D"]) * 4 + G * d["D"] * ib


def _fd_cost(config, d, dtype):
    B, S, H, KV, D = d["B"], d["S"], d["H"], d["KV"], d["D"]
    G = max(H // KV, 1)
    bk = min(config["block_kv"], S)
    nk = math.ceil(S / bk)
    n_steps = B * KV * nk
    ib = _dtype_bytes(dtype)
    flops = n_steps * 4.0 * G * bk * D * _align_penalty(bk, dtype)
    hbm = 2.0 * B * KV * nk * bk * D * ib  # stream the cache once
    vmem = _fd_vmem(config, d, dtype)
    return _roofline_s(flops, hbm, n_steps, vmem, config, bk * D)


# -- gated linear attention --------------------------------------------------
def _gla_space() -> ParameterSpace:
    return _with_launch_knobs([
        EnumParam("chunk", (16, 32, 64, 128, 256), 128),
    ])


def _gla_inputs(d, dtype, rng):
    q = _rand(rng, (d["B"], d["S"], d["H"], d["DK"]), dtype)
    k = _rand(rng, (d["B"], d["S"], d["H"], d["DK"]), dtype)
    v = _rand(rng, (d["B"], d["S"], d["H"], d["DV"]), dtype)
    import jax.numpy as jnp

    g = jnp.asarray(-np.abs(rng.normal(size=(d["B"], d["S"], d["H"])) * 0.3),
                    jnp.float32)
    return q, k, v, g


def _gla_call(inputs, config, interpret):
    from repro.kernels.gla import gla_pallas

    q, k, v, g = inputs
    return gla_pallas(q, k, v, g, chunk=config["chunk"],
                      interpret=interpret, **_launch_kw(config))[0]


def _gla_vmem(config, d, dtype):
    """Resident tiles: q/k/v/g chunk + f32 recurrent state + (L,L) scores."""
    L = min(config["chunk"], d["S"])
    DK, DV = d["DK"], d["DV"]
    ib = _dtype_bytes(dtype)
    return (L * (2 * DK + 2 * DV) + L) * ib + DK * DV * 4 + L * L * 4


def _gla_cost(config, d, dtype):
    B, S, H, DK, DV = d["B"], d["S"], d["H"], d["DK"], d["DV"]
    L = min(config["chunk"], S)
    nc = math.ceil(S / L)
    n_steps = B * H * nc
    ib = _dtype_bytes(dtype)
    pad = _align_penalty(L, dtype)
    # intra-chunk (L,L)x(L,dv) + qk^T + state update, all MXU work
    flops = n_steps * (2.0 * L * L * DK + 2.0 * L * L * DV
                       + 4.0 * L * DK * DV) * pad
    hbm = n_steps * L * (2 * DK + 2 * DV + 1) * ib
    vmem = _gla_vmem(config, d, dtype)
    return _roofline_s(flops, hbm, n_steps, vmem, config, L * L)


# -- rmsnorm -----------------------------------------------------------------
def _rn_space() -> ParameterSpace:
    return _with_launch_knobs([
        EnumParam("block_rows", (8, 16, 32, 64, 128, 256, 512, 1024), 256),
    ])


def _rn_inputs(d, dtype, rng):
    x = _rand(rng, (d["ROWS"], d["D"]), dtype)
    import jax.numpy as jnp

    s = jnp.asarray(rng.normal(size=(d["D"],)), jnp.float32)
    return x, s


def _rn_call(inputs, config, interpret):
    from repro.kernels.rmsnorm import rmsnorm_pallas

    x, s = inputs
    return rmsnorm_pallas(x, s, block_rows=config["block_rows"],
                          interpret=interpret, **_launch_kw(config))


def _rn_vmem(config, d, dtype):
    """Resident tiles: input + output row blocks (f32 accumulate) + scale."""
    br = min(config["block_rows"], d["ROWS"])
    return 2 * br * d["D"] * max(_dtype_bytes(dtype), 4) + d["D"] * 4


def _rn_cost(config, d, dtype):
    rows, D = d["ROWS"], d["D"]
    br = min(config["block_rows"], rows)
    n = math.ceil(rows / br)
    ib = _dtype_bytes(dtype)
    pad = _align_penalty(br, dtype)
    flops = n * 4.0 * br * D * pad  # VPU work; counted at MXU scale below
    hbm = 2.0 * rows * D * ib + n * D * 4
    vmem = _rn_vmem(config, d, dtype)
    # rmsnorm is pure VPU: scale compute down to VPU throughput (~1/8 MXU)
    return _roofline_s(flops * 8.0, hbm, n, vmem, config, br * D)


# -- paged decode attention --------------------------------------------------
# the authoritative page granularity (serve/paging.py is numpy-only, so
# this import stays cheap and the two can never drift)
from repro.serve.paging import PAGE_TOKENS, block_groups  # noqa: E402


def _pa_space() -> ParameterSpace:
    # pages_per_block is the pool-layout granularity: a page group holds
    # pages_per_block * PAGE_TOKENS tokens, and the kernel streams as many
    # whole groups a grid step as fill its block.  The serve engine's
    # paged allocator adopts the tuned value as its group size, so the
    # knob couples kernel tiling with allocator fragmentation.
    return _with_launch_knobs([
        EnumParam("pages_per_block", (1, 2, 4, 8, 16, 32), 4),
    ])


def _pa_inputs(d, dtype, rng):
    # Dense K/V + lengths; the call adapter lays the pool out at the
    # candidate pages_per_block (layout is part of the config under test).
    q = _rand(rng, (d["B"], d["H"], d["D"]), dtype)
    k = _rand(rng, (d["B"], d["S"], d["KV"], d["D"]), dtype)
    v = _rand(rng, (d["B"], d["S"], d["KV"], d["D"]), dtype)
    return q, k, v, d["S"]


def _pa_call(inputs, config, interpret):
    import jax.numpy as jnp

    from repro.kernels.paged_attention import paged_flash_decode_pallas

    q, k, v, kv_len = inputs
    B, S, KV, D = k.shape
    T = int(config["pages_per_block"]) * PAGE_TOKENS
    pad = (-S) % T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    maxg = k.shape[1] // T
    # head-major pool: (groups, KV, T, D)
    k_pages = k.reshape(B * maxg, T, KV, D).transpose(0, 2, 1, 3)
    v_pages = v.reshape(B * maxg, T, KV, D).transpose(0, 2, 1, 3)
    pt = jnp.arange(B * maxg, dtype=jnp.int32).reshape(B, maxg)
    lengths = jnp.full((B,), kv_len, jnp.int32)
    return paged_flash_decode_pallas(
        q, k_pages, v_pages, pt, lengths,
        dimension_semantics=config.get("dim_semantics"),
        num_warps=config.get("num_warps"), interpret=interpret)


def _pa_tiling(config, d, dtype):
    """(T, MAXG, n): group tokens, groups per slot, groups per grid step
    — the kernel's own rule for ``n``, so model and kernel agree."""
    T = min(int(config["pages_per_block"]) * PAGE_TOKENS, d["S"])
    maxg = math.ceil(d["S"] / T)
    return T, maxg, block_groups(d["KV"], T, d["D"], _dtype_bytes(dtype),
                                 maxg)


def _pa_vmem(config, d, dtype):
    """Resident tiles: the double-buffered K and V blocks of ``n`` whole
    groups (all KV heads), f32 m/l/acc for every query head, and the
    pipelined query and output blocks of one slot."""
    T, _, n = _pa_tiling(config, d, dtype)
    ib = _dtype_bytes(dtype)
    KV, D = d["KV"], d["D"]
    G = max(d["H"] // KV, 1)
    return (2 * 2 * n * KV * T * D * ib + KV * G * (2 + D) * 4
            + 2 * 2 * KV * G * D * ib)


def _pa_cost(config, d, dtype):
    B, H, KV, D = d["B"], d["H"], d["KV"], d["D"]
    G = max(H // KV, 1)
    T, maxg, n = _pa_tiling(config, d, dtype)
    n_steps = B * math.ceil(maxg / n)
    ib = _dtype_bytes(dtype)
    # every step computes a whole block of n groups, all heads
    flops = n_steps * KV * 4.0 * G * n * T * D * _align_penalty(T, dtype)
    # the inputs hold S valid tokens a slot: whole groups of all heads,
    # one DMA descriptor (K and V) per group
    hbm = 2.0 * B * maxg * KV * T * D * ib + 2 * B * maxg * 64.0
    vmem = _pa_vmem(config, d, dtype)
    return _roofline_s(flops, hbm, n_steps, vmem, config, n * KV * T * D)


KERNELS: Dict[str, KernelDef] = {
    # SK = KV sequence length; distinct from S so cross-attention and
    # cache-prefill problems (different KV lengths, same query length) key
    # separate autotune entries.
    "flash_attention": KernelDef(
        "flash_attention", ("B", "S", "SK", "H", "KV", "D"),
        ("block_q", "block_kv", "dim_semantics"),
        _fa_space, _fa_inputs, _fa_call, _fa_cost, _fa_vmem),
    "decode_attention": KernelDef(
        "decode_attention", ("B", "S", "H", "KV", "D"),
        ("block_kv", "dim_semantics"),
        _fd_space, _fd_inputs, _fd_call, _fd_cost, _fd_vmem),
    "paged_attention": KernelDef(
        "paged_attention", ("B", "S", "H", "KV", "D"),
        ("pages_per_block", "dim_semantics"),
        _pa_space, _pa_inputs, _pa_call, _pa_cost, _pa_vmem),
    "gla": KernelDef(
        "gla", ("B", "S", "H", "DK", "DV"),
        ("chunk", "dim_semantics"),
        _gla_space, _gla_inputs, _gla_call, _gla_cost, _gla_vmem),
    "rmsnorm": KernelDef(
        "rmsnorm", ("ROWS", "D"),
        ("block_rows", "dim_semantics"),
        _rn_space, _rn_inputs, _rn_call, _rn_cost, _rn_vmem),
}


class KernelSpace:
    """The ACTS parameter space of one kernel's tiling knobs."""

    def __init__(self, kernel: str):
        if kernel not in KERNELS:
            raise ValueError(f"unknown kernel {kernel!r}; "
                             f"have {sorted(KERNELS)}")
        self.kernel = kernel
        self.definition = KERNELS[kernel]

    def space(self) -> ParameterSpace:
        return self.definition.make_space()

    @property
    def knobs(self) -> Tuple[str, ...]:
        return self.definition.knobs

    def validate_dims(self, dims: Dict[str, int]) -> Dict[str, int]:
        missing = [k for k in self.definition.dims if k not in dims]
        if missing:
            raise ValueError(
                f"kernel {self.kernel}: missing dims {missing}")
        return {k: int(dims[k]) for k in self.definition.dims}
