"""Model assembly: superblock scan, embeddings, losses, KV-cache decode.

Every architecture is a repeating *superblock* pattern (configs define it:
e.g. gemma3 = 5×swa + 1×attn; llama-vision = 4×attn + 1×cross; zamba2 =
9×mamba2 + shared + 9×mamba2).  The stack executes as ``lax.scan`` over
parameters stacked along a leading "layer" axis — O(superblock) HLO instead
of O(n_layers), which is what keeps 100-layer × 512-device compiles
tractable and is the production-correct choice on TPU.

Weight-shared blocks (Zamba2's shared attention) live *outside* the scanned
stack and are closure-captured, so every superblock invocation reuses the
same weights while keeping per-invocation KV caches (cache slots are keyed
by position, stacked under the scan).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig, ShapeSpec
from repro.dist.sharding import constrain
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import (
    ParamDef,
    abstract_params,
    count_def_params,
    cross_entropy_loss,
    dtype_of,
    init_params,
    normal_init,
    ones_init,
    param_specs,
    rms_norm,
    stack_defs,
)

__all__ = ["Model", "count_params", "model_defs"]

ATTN_KINDS = {"attn", "swa", "moe", "moe_swa", "dec", "shared"}


# ---------------------------------------------------------------------------
# parameter definitions
# ---------------------------------------------------------------------------
def _norm_def(cfg: ModelConfig) -> ParamDef:
    return ParamDef((cfg.d_model,), (None,), ones_init(), jnp.float32)


def block_defs(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    if kind in ("attn", "swa"):
        return {
            "ln1": _norm_def(cfg),
            "attn": attn_mod.attention_defs(cfg),
            "ln2": _norm_def(cfg),
            "mlp": mlp_mod.mlp_defs(cfg),
        }
    if kind in ("moe", "moe_swa"):
        return {
            "ln1": _norm_def(cfg),
            "attn": attn_mod.attention_defs(cfg),
            "ln2": _norm_def(cfg),
            "moe": moe_mod.moe_defs(cfg),
        }
    if kind == "cross":
        return {
            "ln1": _norm_def(cfg),
            "xattn": attn_mod.attention_defs(cfg, cross=True),
            "ln2": _norm_def(cfg),
            "mlp": mlp_mod.mlp_defs(cfg),
            "gate": ParamDef((1,), (None,), lambda k, s, d: jnp.zeros(s, d),
                             jnp.float32),
        }
    if kind == "dec":
        return {
            "ln1": _norm_def(cfg),
            "attn": attn_mod.attention_defs(cfg),
            "ln_x": _norm_def(cfg),
            "xattn": attn_mod.attention_defs(cfg, cross=True),
            "ln2": _norm_def(cfg),
            "mlp": mlp_mod.mlp_defs(cfg),
        }
    if kind == "mamba2":
        return {"ln1": _norm_def(cfg), "mixer": ssm_mod.mamba2_defs(cfg)}
    if kind == "mlstm":
        return {"ln1": _norm_def(cfg), "mixer": xlstm_mod.mlstm_defs(cfg)}
    if kind == "slstm":
        return {"ln1": _norm_def(cfg), "mixer": xlstm_mod.slstm_defs(cfg)}
    if kind == "shared":
        return {}  # weights live at the top level (model_defs)
    raise ValueError(f"unknown block kind {kind!r}")


def superblock_defs(cfg: ModelConfig, pattern: Tuple[str, ...]) -> Dict[str, Any]:
    return {f"{i}_{kind}": block_defs(cfg, kind)
            for i, kind in enumerate(pattern)}


def model_defs(cfg: ModelConfig) -> Dict[str, Any]:
    d, V = cfg.d_model, cfg.padded_vocab
    pdt = dtype_of(cfg.param_dtype)
    defs: Dict[str, Any] = {
        "embed": ParamDef((V, d), ("vocab", "embed"), normal_init(0.02), pdt),
        "blocks": stack_defs(superblock_defs(cfg, cfg.superblock),
                             cfg.n_superblocks),
        "final_norm": _norm_def(cfg),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, V), ("embed_fsdp", "vocab"),
                                   normal_init(0.02), pdt)
    if "shared" in cfg.superblock:
        defs["shared"] = {
            "ln1": _norm_def(cfg),
            "attn": attn_mod.attention_defs(cfg),
            "ln2": _norm_def(cfg),
            "mlp": mlp_mod.mlp_defs(cfg),
        }
    if cfg.encoder:
        enc_sb = superblock_defs(cfg, cfg.encoder.superblock)
        n_enc_sb = cfg.encoder.n_layers // len(cfg.encoder.superblock)
        defs["encoder"] = {
            "blocks": stack_defs(enc_sb, n_enc_sb),
            "final_norm": _norm_def(cfg),
        }
    if cfg.frontend:
        defs["frontend_proj"] = ParamDef(
            (cfg.frontend_dim, d), (None, "embed"), normal_init(0.02), pdt)
    return defs


def count_params(cfg: ModelConfig) -> int:
    return count_def_params(model_defs(cfg))


# ---------------------------------------------------------------------------
# cache definitions
# ---------------------------------------------------------------------------
def cache_block_defs(cfg: ModelConfig, kind: str, batch: int,
                     max_seq: int) -> Dict[str, Any]:
    if kind in ("attn", "moe", "dec", "shared"):
        return attn_mod.init_attn_cache_defs(cfg, batch, max_seq)
    if kind in ("swa", "moe_swa"):
        return attn_mod.init_attn_cache_defs(cfg, batch, max_seq,
                                             window=cfg.window)
    if kind == "cross":
        return {}  # cross K/V recomputed from cached memory
    if kind == "mamba2":
        return ssm_mod.mamba2_cache_defs(cfg, batch)
    if kind == "mlstm":
        return xlstm_mod.mlstm_cache_defs(cfg, batch)
    if kind == "slstm":
        return xlstm_mod.slstm_cache_defs(cfg, batch)
    raise ValueError(f"unknown block kind {kind!r}")


def paged_cache_block_defs(cfg: ModelConfig, kind: str, n_groups: int,
                           group_tokens: int) -> Dict[str, Any]:
    """KV pool shapes for one block under the paged layout: requests own
    page *groups* instead of dense per-slot buffers.  Only dense-cache
    attention kinds are pageable (``Model.supports_continuous_batching``
    gates the rest to the wave runtime)."""
    if kind in ("attn", "moe", "dec", "shared"):
        from repro.kernels.paged_attention import POOL_AXES
        from repro.models.common import zeros_init

        KV, Dh = cfg.n_kv_heads, cfg.head_dim_
        dt = dtype_of(cfg.compute_dtype)
        # POOL_AXES is the paged kernel's layout contract: only the
        # kv_heads axis may shard (model-axis TP); groups stay whole so
        # the page-table index_map addresses every shard identically.
        return {
            "k": ParamDef((n_groups, KV, group_tokens, Dh), POOL_AXES,
                          zeros_init(), dt),
            "v": ParamDef((n_groups, KV, group_tokens, Dh), POOL_AXES,
                          zeros_init(), dt),
        }
    if kind == "cross":
        return {}  # cross K/V recomputed from cached memory
    raise ValueError(f"block kind {kind!r} has no paged cache layout")


def paged_cache_defs(cfg: ModelConfig, n_groups: int,
                     group_tokens: int) -> Dict[str, Any]:
    sb = {f"{i}_{kind}": paged_cache_block_defs(cfg, kind, n_groups,
                                                group_tokens)
          for i, kind in enumerate(cfg.superblock)}
    return {"blocks": stack_defs(sb, cfg.n_superblocks)}


def cache_defs(cfg: ModelConfig, batch: int, max_seq: int) -> Dict[str, Any]:
    sb = {f"{i}_{kind}": cache_block_defs(cfg, kind, batch, max_seq)
          for i, kind in enumerate(cfg.superblock)}
    defs: Dict[str, Any] = {
        "blocks": stack_defs(sb, cfg.n_superblocks),
        "index": ParamDef((), (), lambda k, s, d: jnp.zeros(s, d), jnp.int32),
    }
    if cfg.frontend or cfg.encoder:
        n_mem = cfg.frontend_tokens if not cfg.encoder else cfg.frontend_tokens
        defs["memory"] = ParamDef(
            (batch, n_mem, cfg.d_model), ("batch", None, "embed"),
            lambda k, s, d: jnp.zeros(s, d), dtype_of(cfg.compute_dtype))
    return defs


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------
def _apply_block_full(kind: str, p: Dict[str, Any], x: jax.Array,
                      ctx: Dict[str, Any], cfg: ModelConfig
                      ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence (train / prefill-without-cache) application."""
    aux = jnp.zeros((), jnp.float32)
    positions = ctx["positions"]
    causal = ctx.get("causal", True)
    if kind == "shared":
        p = ctx["shared_params"]
        kind = "attn"

    if kind in ("attn", "swa", "moe", "moe_swa"):
        window = cfg.window if kind in ("swa", "moe_swa") else 0
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, _ = attn_mod.self_attention(
            p["attn"], h, cfg=cfg, positions=positions, window=window,
            impl=None if causal else "dense", causal=causal)
        x = x + y
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if kind in ("moe", "moe_swa"):
            y, aux = moe_mod.moe_ffn(p["moe"], h, cfg,
                                     group_size=ctx.get("moe_group", 4096))
        else:
            y = mlp_mod.mlp(p["mlp"], h, cfg)
        return x + y, aux

    if kind == "cross":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, _ = attn_mod.cross_attention(p["xattn"], h, ctx["memory"], cfg=cfg)
        x = x + jnp.tanh(p["gate"]).astype(x.dtype) * y
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + mlp_mod.mlp(p["mlp"], h, cfg), aux

    if kind == "dec":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, _ = attn_mod.self_attention(p["attn"], h, cfg=cfg,
                                       positions=positions)
        x = x + y
        h = rms_norm(x, p["ln_x"], cfg.norm_eps)
        y, _ = attn_mod.cross_attention(p["xattn"], h, ctx["memory"], cfg=cfg)
        x = x + y
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + mlp_mod.mlp(p["mlp"], h, cfg), aux

    if kind in ("mamba2", "mlstm", "slstm"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        fn = {"mamba2": ssm_mod.mamba2_block, "mlstm": xlstm_mod.mlstm_block,
              "slstm": xlstm_mod.slstm_block}[kind]
        return x + fn(p["mixer"], h, cfg), aux

    raise ValueError(f"unknown block kind {kind!r}")


def _apply_block_decode(kind: str, p: Dict[str, Any], x: jax.Array,
                        cache: Dict[str, Any], ctx: Dict[str, Any],
                        cfg: ModelConfig
                        ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Single-token decode with cache update."""
    positions = ctx["positions"]
    index = ctx["index"]
    if kind == "shared":
        p = ctx["shared_params"]
        kind = "attn"

    if kind in ("attn", "swa", "moe", "moe_swa"):
        window = cfg.window if kind in ("swa", "moe_swa") else 0
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, new_kv = attn_mod.self_attention(
            p["attn"], h, cfg=cfg, positions=positions, window=window,
            cache=cache, cache_index=index,
            page_table=ctx.get("page_table"))
        x = x + y
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if kind in ("moe", "moe_swa"):
            y, _ = moe_mod.moe_ffn(p["moe"], h, cfg)
        else:
            y = mlp_mod.mlp(p["mlp"], h, cfg)
        return x + y, new_kv

    if kind == "cross":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, _ = attn_mod.cross_attention(p["xattn"], h, ctx["memory"], cfg=cfg)
        x = x + jnp.tanh(p["gate"]).astype(x.dtype) * y
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + mlp_mod.mlp(p["mlp"], h, cfg), cache

    if kind == "dec":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, new_kv = attn_mod.self_attention(
            p["attn"], h, cfg=cfg, positions=positions, cache=cache,
            cache_index=index, page_table=ctx.get("page_table"))
        x = x + y
        h = rms_norm(x, p["ln_x"], cfg.norm_eps)
        y, _ = attn_mod.cross_attention(p["xattn"], h, ctx["memory"], cfg=cfg)
        x = x + y
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + mlp_mod.mlp(p["mlp"], h, cfg), new_kv

    if kind in ("mamba2", "mlstm", "slstm"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        fn = {"mamba2": ssm_mod.mamba2_decode, "mlstm": xlstm_mod.mlstm_decode,
              "slstm": xlstm_mod.slstm_decode}[kind]
        y, new_cache = fn(p["mixer"], h, cache, cfg)
        return x + y, new_cache

    raise ValueError(f"unknown block kind {kind!r}")


# ---------------------------------------------------------------------------
# stack execution (scan over superblocks)
# ---------------------------------------------------------------------------
def _remat_wrap(fn, remat: str):
    if remat == "none":
        return fn
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    raise ValueError(f"unknown remat policy {remat!r}")


def _stack_forward(blocks_params, x, ctx, cfg: ModelConfig,
                   pattern: Tuple[str, ...], remat: str = "none"):
    def superblock(x, sb_params):
        aux = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(pattern):
            x, a = _apply_block_full(kind, sb_params[f"{i}_{kind}"], x, ctx, cfg)
            aux = aux + a
        return x, aux

    wrapped = _remat_wrap(superblock, remat)

    def body(carry, sb_params):
        x, aux = carry
        x, a = wrapped(x, sb_params)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               blocks_params)
    return x, aux


def _stack_decode(blocks_params, blocks_cache, x, ctx, cfg: ModelConfig):
    pattern = cfg.superblock

    def body(x, inputs):
        sb_params, sb_cache = inputs
        new_sb_cache = {}
        for i, kind in enumerate(pattern):
            key = f"{i}_{kind}"
            x, new_sb_cache[key] = _apply_block_decode(
                kind, sb_params[key], x, sb_cache[key], ctx, cfg)
        return x, new_sb_cache

    x, new_cache = jax.lax.scan(body, x, (blocks_params, blocks_cache))
    return x, new_cache


# ---------------------------------------------------------------------------
# the Model
# ---------------------------------------------------------------------------
class Model:
    """Pure-function model bound to a ModelConfig."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self._defs = model_defs(cfg)

    # --- parameters -----------------------------------------------------
    def param_defs(self):
        return self._defs

    def init(self, rng: jax.Array):
        return init_params(self._defs, rng)

    def abstract_params(self):
        return abstract_params(self._defs)

    def param_specs(self, rules, mesh):
        return param_specs(self._defs, rules, mesh)

    # --- embedding / head -------------------------------------------------
    def _embed(self, params, tokens):
        cdt = dtype_of(self.cfg.compute_dtype)
        x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
        x = x * jnp.asarray(math.sqrt(self.cfg.d_model), cdt)
        return constrain(x, "batch", "seq", "embed")

    def _logits(self, params, x):
        cdt = dtype_of(self.cfg.compute_dtype)
        if self.cfg.tie_embeddings:
            w = params["embed"].astype(cdt)  # (V, d)
            logits = jnp.einsum("bsd,vd->bsv", x.astype(cdt), w)
        else:
            logits = jnp.einsum("bsd,dv->bsv", x.astype(cdt),
                                params["lm_head"].astype(cdt))
        return constrain(logits, "batch", "seq", "vocab")

    def _memory(self, params, batch) -> Optional[jax.Array]:
        """Projected cross-attention memory from the modality frontend stub
        and/or the encoder."""
        cfg = self.cfg
        if not (cfg.frontend or cfg.encoder):
            return None
        embeds = batch["frontend_embeds"]  # (B, n_tok, frontend_dim) STUB input
        cdt = dtype_of(cfg.compute_dtype)
        mem = jnp.einsum("bnf,fd->bnd", embeds.astype(cdt),
                         params["frontend_proj"].astype(cdt))
        if cfg.encoder:
            enc_pos = jnp.arange(mem.shape[1])
            ctx = {"positions": enc_pos, "causal": False, "memory": None}
            n_enc_sb = cfg.encoder.n_layers // len(cfg.encoder.superblock)
            mem, _ = _stack_forward(params["encoder"]["blocks"], mem, ctx, cfg,
                                    cfg.encoder.superblock)
            mem = rms_norm(mem, params["encoder"]["final_norm"], cfg.norm_eps)
        return constrain(mem, "batch", None, "embed")

    # --- full-sequence forward (train) -----------------------------------
    def forward(self, params, batch, *, remat: str = "none",
                moe_group: int = 4096):
        """batch: tokens (B,S) [+ frontend_embeds] -> (hidden, aux_loss)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = self._embed(params, tokens)
        ctx = {
            "positions": jnp.arange(S),
            "memory": self._memory(params, batch),
            "causal": True,
            "moe_group": moe_group,
            "shared_params": params.get("shared"),
        }
        x, aux = _stack_forward(params["blocks"], x, ctx, cfg, cfg.superblock,
                                remat=remat)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, aux

    def loss(self, params, batch, *, remat: str = "none",
             loss_chunk: int = 0, moe_group: int = 4096,
             aux_weight: float = 0.01):
        """Causal LM loss. ``loss_chunk > 0`` computes the cross-entropy in
        sequence chunks so the full (B,S,V) logits tensor never materializes."""
        cfg = self.cfg
        x, aux = self.forward(params, batch, remat=remat, moe_group=moe_group)
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = (labels >= 0).astype(jnp.float32)
        labels = jnp.maximum(labels, 0)
        V = cfg.padded_vocab
        vocab_valid = (jnp.arange(V) < cfg.vocab_size)

        def chunk_loss(x_c, labels_c, mask_c):
            logits = self._logits(params, x_c)
            logits = jnp.where(vocab_valid[None, None, :], logits, -1e30)
            lg = logits.astype(jnp.float32)
            logz = jax.scipy.special.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, labels_c[..., None], axis=-1)[..., 0]
            nll = (logz - gold) * mask_c
            acc = ((lg.argmax(-1) == labels_c) * mask_c)
            return nll.sum(), acc.sum()

        if loss_chunk and x.shape[1] > loss_chunk and x.shape[1] % loss_chunk == 0:
            nchunk = x.shape[1] // loss_chunk
            xs = (x.reshape(x.shape[0], nchunk, loss_chunk, -1).swapaxes(0, 1),
                  labels.reshape(labels.shape[0], nchunk, loss_chunk).swapaxes(0, 1),
                  mask.reshape(mask.shape[0], nchunk, loss_chunk).swapaxes(0, 1))

            def body(carry, inp):
                nll, acc = chunk_loss(*inp)
                return (carry[0] + nll, carry[1] + acc), None

            (nll_sum, acc_sum), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                xs)
        else:
            nll_sum, acc_sum = chunk_loss(x, labels, mask)

        denom = jnp.maximum(mask.sum(), 1.0)
        loss = nll_sum / denom
        total = loss + aux_weight * aux
        metrics = {"loss": loss, "aux_loss": aux, "accuracy": acc_sum / denom,
                   "tokens": denom}
        return total, metrics

    # --- serving ----------------------------------------------------------
    def cache_defs(self, batch: int, max_seq: int):
        return cache_defs(self.cfg, batch, max_seq)

    def init_cache(self, batch: int, max_seq: int):
        return init_params(self.cache_defs(batch, max_seq),
                           jax.random.PRNGKey(0))

    def abstract_cache(self, batch: int, max_seq: int):
        return abstract_params(self.cache_defs(batch, max_seq))

    def cache_specs(self, batch: int, max_seq: int, rules, mesh):
        return param_specs(self.cache_defs(batch, max_seq), rules, mesh)

    def prefill(self, params, batch, cache):
        """Run the prompt through the model, filling the KV caches.

        Returns (last-token logits, cache).  Attention runs in full-sequence
        mode (blocked/local), and K/V are written into the cache buffers —
        ring-rolled for sliding-window blocks.
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = self._embed(params, tokens)
        memory = self._memory(params, batch)
        ctx = {
            "positions": jnp.arange(S),
            "memory": memory,
            "causal": True,
            "shared_params": params.get("shared"),
        }

        pattern = cfg.superblock

        def body(x, inputs):
            sb_params, sb_cache = inputs
            new_sb = {}
            for i, kind in enumerate(pattern):
                key = f"{i}_{kind}"
                p = sb_params[key] if kind != "shared" else ctx["shared_params"]
                akind = "attn" if kind == "shared" else kind
                if akind in ("attn", "swa", "moe", "moe_swa", "dec"):
                    window = cfg.window if akind in ("swa", "moe_swa") else 0
                    h = rms_norm(x, p["ln1"], cfg.norm_eps)
                    q, k, v = attn_mod._project_qkv(p["attn"], h, None, cfg)
                    from repro.models.common import apply_rope, rope_freqs

                    cos, sin = rope_freqs(ctx["positions"], cfg.head_dim_,
                                          cfg.rope_theta)
                    q = apply_rope(q, cos, sin)
                    k = apply_rope(k, cos, sin)
                    y = attn_mod.attend(q, k, v, cfg=cfg, causal=True,
                                        window=window)
                    y = attn_mod._mask_padded_heads(y, cfg)
                    cdt = dtype_of(cfg.compute_dtype)
                    y = jnp.einsum("bshk,hkd->bsd", y.astype(cdt),
                                   p["attn"]["wo"].astype(cdt))
                    x = x + y
                    new_sb[key] = _write_prefill_kv(
                        sb_cache[key], k, v, window, S)
                    if akind == "dec":
                        h = rms_norm(x, p["ln_x"], cfg.norm_eps)
                        y, _ = attn_mod.cross_attention(p["xattn"], h, memory,
                                                        cfg=cfg)
                        x = x + y
                    h = rms_norm(x, p["ln2"], cfg.norm_eps)
                    if akind in ("moe", "moe_swa"):
                        y, _ = moe_mod.moe_ffn(p["moe"], h, cfg)
                    else:
                        y = mlp_mod.mlp(p["mlp"], h, cfg)
                    x = x + y
                elif akind == "cross":
                    x, _ = _apply_block_full("cross", p, x, ctx, cfg)
                    new_sb[key] = sb_cache[key]
                else:  # recurrent blocks: run full-seq then recompute state
                    x, new_sb[key] = _prefill_recurrent(akind, p, x, sb_cache[key],
                                                        cfg)
            return x, new_sb

        x, new_blocks = jax.lax.scan(body, x, (params["blocks"],
                                               cache["blocks"]))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._logits(params, x[:, -1:, :])
        new_cache = dict(cache, blocks=new_blocks,
                         index=jnp.asarray(S, jnp.int32))
        if memory is not None and "memory" in cache:
            new_cache["memory"] = memory.astype(cache["memory"].dtype)
        return logits, new_cache

    @property
    def supports_chunked_prefill(self) -> bool:
        """Whether the prompt can be prefilled in segments through the cache.

        Chunked prefill appends multi-token segments via the cached-attention
        path, which is exact for dense-cache attention blocks (attn / moe /
        dec / cross / shared).  Sliding-window blocks write a ring buffer
        whose multi-token append would wrap incorrectly, and recurrent
        mixers (mamba2 / mlstm / slstm) recompute their state from the full
        sequence — both prefill whole prompts instead.

        MoE blocks are chunk-exact only with drop-free router capacity
        (``capacity_factor * experts_per_token >= n_experts``): capacity-
        bound routing drops tokens per routing *group*, and the grouping
        differs between whole-prompt and per-chunk prefill, so a capacity-
        bound MoE would generate different tokens under chunking.
        """
        kinds = set(self.cfg.superblock)
        if not kinds <= {"attn", "moe", "dec", "cross", "shared"}:
            return False
        if "moe" in kinds:
            moe = self.cfg.moe
            if moe is None or (moe.capacity_factor * moe.experts_per_token
                               < moe.n_experts):
                return False
        return True

    def prefill_chunk(self, params, batch, cache):
        """Append one prompt segment to the KV caches (chunked prefill).

        ``batch["tokens"]``: (B, C) — the next C prompt tokens;
        ``cache["index"]`` tokens are already resident.  For
        frontend/encoder models, ``frontend_embeds`` MUST ride with the
        FIRST chunk: the projected memory is computed once, carried in the
        cache, and reused by later chunks — a first chunk without it would
        silently attend to the cache's zero-initialized memory buffer
        (``ServeEngine.generate`` validates this up front; direct callers
        own the contract, since the chunk index is traced and cannot be
        checked here).  Returns (last-token logits, cache) —
        value-equivalent to whole-prompt ``prefill`` for stacks where
        ``supports_chunked_prefill`` holds, so the serve engine's
        ``prefill_chunk`` knob changes *timing*, not tokens.
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, C = tokens.shape
        index = cache["index"]
        if "frontend_embeds" in batch:
            memory = self._memory(params, batch)
        else:
            memory = cache.get("memory")
        x = self._embed(params, tokens)
        ctx = {
            "positions": index + jnp.arange(C),
            "index": index,
            "memory": memory,
            "shared_params": params.get("shared"),
        }
        x, new_blocks = _stack_decode(params["blocks"], cache["blocks"], x,
                                      ctx, cfg)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._logits(params, x[:, -1:, :])
        new_cache = dict(cache, blocks=new_blocks, index=index + C)
        if memory is not None and "memory" in cache:
            new_cache["memory"] = memory.astype(cache["memory"].dtype)
        return logits, new_cache

    def decode_step(self, params, tokens, cache):
        """One decode step: tokens (B, 1) + cache -> (logits, new cache)."""
        cfg = self.cfg
        B = tokens.shape[0]
        index = cache["index"]
        x = self._embed(params, tokens)
        positions = jnp.broadcast_to(index, (B, 1))
        ctx = {
            "positions": positions,
            "index": index,
            "memory": cache.get("memory"),
            "shared_params": params.get("shared"),
        }
        x, new_blocks = _stack_decode(params["blocks"], cache["blocks"], x,
                                      ctx, cfg)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._logits(params, x)
        new_cache = dict(cache, blocks=new_blocks, index=index + 1)
        return logits, new_cache

    # --- continuous batching ----------------------------------------------
    @property
    def supports_continuous_batching(self) -> bool:
        """Continuous batching decodes slots at per-slot cache lengths and
        prefills admitted requests through the chunked-append path, so it
        is exact for precisely the stacks chunked prefill is exact for
        (sliding-window rings and recurrent mixers keep the wave loop)."""
        return self.supports_chunked_prefill

    def init_paged_cache(self, n_groups: int, group_tokens: int):
        """KV pools for the paged layout: ``{"blocks": ...}`` with
        head-major (n_groups, KV, group_tokens, D) pools per attention
        block (the paged kernel's block layout).  The
        page table and per-slot lengths live with the engine — group 0 is
        the allocator's scratch group (idle decode lanes write there)."""
        return init_params(paged_cache_defs(self.cfg, n_groups,
                                            group_tokens),
                           jax.random.PRNGKey(0))

    def paged_cache_specs(self, n_groups: int, group_tokens: int, rules,
                          mesh):
        """PartitionSpecs matching ``init_paged_cache``'s tree: page
        groups stay whole per device, the KV-head axis follows the rule
        table's model-axis split (``POOL_AXES``)."""
        return param_specs(paged_cache_defs(self.cfg, n_groups,
                                            group_tokens), rules, mesh)

    def decode_step_multi(self, params, tokens, cache, lengths,
                          page_table=None):
        """Continuous-batching decode: C token(s) per slot, each slot at
        its OWN cache length.

        ``tokens``: (B, C); ``lengths``: (B,) tokens already resident per
        slot.  C == 1 is the ordinary decode step; C > 1 is the
        speculative-verify dispatch — column i of slot b sits at position
        ``lengths[b] + i``, and the causal per-slot masks make each
        column's logits exactly what C successive single-token steps
        would produce, so acceptance can compare draft tokens against
        bit-stable verified ones.  Dense layout (``page_table=None``):
        ``cache["blocks"]`` are the usual per-slot buffers, appended by
        scatter.  Paged layout: the blocks are pools and ``page_table``
        (B, MAXG) maps each slot's logical groups to physical ones.
        Idle/masked slots are decoded too (their outputs are discarded by
        the engine) — slot math is row-independent, so live slots' tokens
        are identical whatever the rest of the batch is doing.
        """
        cfg = self.cfg
        lengths = jnp.asarray(lengths, jnp.int32)
        x = self._embed(params, tokens)
        C = tokens.shape[1]
        ctx = {
            "positions": lengths[:, None] + jnp.arange(C,
                                                       dtype=jnp.int32)[None],
            "index": lengths,
            "memory": cache.get("memory"),
            "shared_params": params.get("shared"),
            "page_table": page_table,
        }
        x, new_blocks = _stack_decode(params["blocks"], cache["blocks"], x,
                                      ctx, cfg)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._logits(params, x)
        return logits, dict(cache, blocks=new_blocks)

    def prefill_chunk_slot(self, params, batch, cache, slot, length):
        """Append one prompt chunk for ONE slot of a batched dense cache.

        ``batch["tokens"]``: (1, C).  Slices the slot's view out of every
        per-slot buffer, runs the exact ``prefill_chunk`` path on it, and
        writes the view back — so admission-time prefill reuses the
        chunked-prefill math byte for byte while the other slots keep
        decoding between chunks.  Returns (last-token logits, cache).
        """
        view = {"blocks": jax.tree_util.tree_map(
            lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, axis=1),
            cache["blocks"]),
            "index": jnp.asarray(length, jnp.int32)}
        if "memory" in cache:
            view["memory"] = jax.lax.dynamic_slice_in_dim(
                cache["memory"], slot, 1, axis=0)
        logits, new_view = self.prefill_chunk(params, batch, view)
        new_cache = dict(cache, blocks=jax.tree_util.tree_map(
            lambda l, nv: jax.lax.dynamic_update_slice_in_dim(
                l, nv.astype(l.dtype), slot, axis=1),
            cache["blocks"], new_view["blocks"]))
        if "memory" in cache:
            new_cache["memory"] = jax.lax.dynamic_update_slice_in_dim(
                cache["memory"],
                new_view["memory"].astype(cache["memory"].dtype),
                slot, axis=0)
        return logits, new_cache

    def prefill_chunk_slot_paged(self, params, batch, cache, page_row,
                                 length, slot=None):
        """Paged-layout slot prefill: gather, exact chunk, scatter back.

        The slot's pages are gathered (through ``page_row``, its page-
        table row) into a dense single-request view, the ordinary
        ``prefill_chunk`` runs on that view, and the C freshly-appended
        positions are scattered back into the pools.  Unallocated logical
        groups point at the scratch group; their garbage is masked by the
        chunk path's length-based attention mask, so the gathered tail is
        inert.  ``slot`` addresses the engine's dense memory buffer for
        frontend/encoder models.
        """
        length = jnp.asarray(length, jnp.int32)
        C = batch["tokens"].shape[1]

        def gather(l):
            g = l[:, page_row]  # (n_sb, MAXG, KV, T, D)
            n_sb, maxg, KV, T, D = g.shape
            return g.transpose(0, 1, 3, 2, 4).reshape(n_sb, 1, maxg * T,
                                                      KV, D)

        view = {"blocks": jax.tree_util.tree_map(gather, cache["blocks"]),
                "index": length}
        if "memory" in cache:
            view["memory"] = jax.lax.dynamic_slice_in_dim(
                cache["memory"], slot, 1, axis=0)
        logits, new_view = self.prefill_chunk(params, batch, view)

        pos = length + jnp.arange(C)

        def scatter(l, nv):
            T = l.shape[3]
            # (n_sb, C, KV, D) -> (C, n_sb, KV, D): the two index arrays
            # are split by a slice, so the indexed dims lead the update
            seg = jax.lax.dynamic_slice_in_dim(nv, length, C, axis=2)[:, 0]
            return l.at[:, page_row[pos // T], :, pos % T].set(
                seg.transpose(1, 0, 2, 3).astype(l.dtype))

        new_cache = dict(cache, blocks=jax.tree_util.tree_map(
            scatter, cache["blocks"], new_view["blocks"]))
        if "memory" in cache:
            new_cache["memory"] = jax.lax.dynamic_update_slice_in_dim(
                cache["memory"],
                new_view["memory"].astype(cache["memory"].dtype),
                slot, axis=0)
        return logits, new_cache


def _write_prefill_kv(cache_slice, k, v, window, S):
    """Write prefill K/V into a cache buffer (ring-rolled for SWA)."""
    kb, vb = cache_slice["k"], cache_slice["v"]
    Sbuf = kb.shape[1]
    if window and Sbuf == window:
        if S >= window:
            # slot(p) = p % W for the last W positions => roll by S % W
            k_last, v_last = k[:, -window:], v[:, -window:]
            shift = S % window
        else:
            # positions 0..S-1 already sit at slots 0..S-1
            k_last = jnp.pad(k, ((0, 0), (0, window - S), (0, 0), (0, 0)))
            v_last = jnp.pad(v, ((0, 0), (0, window - S), (0, 0), (0, 0)))
            shift = 0
        kb = jnp.roll(k_last.astype(kb.dtype), shift, axis=1)
        vb = jnp.roll(v_last.astype(vb.dtype), shift, axis=1)
        return {"k": kb, "v": vb}
    S_w = min(S, Sbuf)
    kb = jax.lax.dynamic_update_slice(kb, k[:, :S_w].astype(kb.dtype),
                                      (0, 0, 0, 0))
    vb = jax.lax.dynamic_update_slice(vb, v[:, :S_w].astype(vb.dtype),
                                      (0, 0, 0, 0))
    return {"k": kb, "v": vb}


def _prefill_recurrent(kind, p, x, cache_slice, cfg):
    """Recurrent blocks (mamba2/mlstm/slstm): full-sequence forward that also
    produces the final state for decode continuation."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "mamba2":
        y, state = _mamba2_with_state(p["mixer"], h, cfg)
    elif kind == "mlstm":
        y, state = _mlstm_with_state(p["mixer"], h, cfg)
    elif kind == "slstm":
        y, state = _slstm_with_state(p["mixer"], h, cfg)
    else:
        raise ValueError(kind)
    return x + y, state


def _mamba2_with_state(params, u, cfg):
    from repro.models.ssm import _causal_conv, _dims, _split_proj, _ssd_inputs
    from repro.models.gla import chunked_gla

    B, S, d = u.shape
    d_inner, nh, hd, ds = _dims(cfg)
    cdt = dtype_of(cfg.compute_dtype)
    zxbcdt = jnp.einsum("bsd,dp->bsp", u.astype(cdt),
                        params["in_proj"].astype(cdt))
    z, xBC, dt_raw = _split_proj(cfg, zxbcdt)
    xBC_f = xBC.astype(jnp.float32)
    conv_tail = jnp.pad(xBC_f, ((0, 0), (cfg.ssm_conv - 1, 0), (0, 0)))[
        :, -(cfg.ssm_conv - 1):, :]
    xBC_c = _causal_conv(xBC_f, params["conv_w"].astype(jnp.float32),
                         params["conv_b"].astype(jnp.float32))
    x, Bm, Cm, dt, log_g = _ssd_inputs(cfg, params, xBC_c, dt_raw)
    xh = x.reshape(B, S, nh, hd)
    q = jnp.broadcast_to(Cm[:, :, None, :], (B, S, nh, ds))
    k = jnp.broadcast_to(Bm[:, :, None, :], (B, S, nh, ds))
    v = xh * dt[..., None]
    y, state = chunked_gla(q, k, v, log_g, chunk=cfg.ssm_chunk)
    y = y + xh * params["D"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(B, S, d_inner)
    y = rms_norm(y, params["norm_scale"], cfg.norm_eps) * jax.nn.silu(
        z.astype(jnp.float32))
    out = jnp.einsum("bsp,pd->bsd", y.astype(cdt), params["out_proj"].astype(cdt))
    return out, {"conv": conv_tail, "state": state}


def _mlstm_with_state(params, u, cfg):
    from repro.models.xlstm import (_causal_conv, _mdims, _mlstm_qkvg,
                                    _mlstm_readout)
    from repro.models.gla import chunked_gla

    B, S, d = u.shape
    d_in, nh, dh = _mdims(cfg)
    cdt = dtype_of(cfg.compute_dtype)
    zx = jnp.einsum("bsd,dp->bsp", u.astype(cdt), params["up_proj"].astype(cdt))
    z, x_in = jnp.split(zx, 2, axis=-1)
    x_f = x_in.astype(jnp.float32)
    conv_tail = jnp.pad(x_f, ((0, 0), (cfg.ssm_conv - 1, 0), (0, 0)))[
        :, -(cfg.ssm_conv - 1):, :]
    xc = _causal_conv(x_f, params["conv_w"].astype(jnp.float32),
                      params["conv_b"].astype(jnp.float32))
    q, k, v, log_f = _mlstm_qkvg(params, xc, cfg)
    v_aug = jnp.concatenate([v, jnp.ones_like(v[..., :1])], axis=-1)
    y_aug, state = chunked_gla(q, k, v_aug, log_f, chunk=cfg.ssm_chunk)
    h = _mlstm_readout(y_aug).reshape(B, S, d_in)
    h = rms_norm(h, params["norm_scale"], cfg.norm_eps)
    h = h * jax.nn.silu(z.astype(jnp.float32))
    out = jnp.einsum("bsp,pd->bsd", h.astype(cdt), params["down_proj"].astype(cdt))
    return out, {"conv": conv_tail, "state": state}


def _slstm_with_state(params, u, cfg):
    from repro.models.xlstm import _slstm_cell

    B, S, d = u.shape
    nh, dh = cfg.n_heads, d // cfg.n_heads
    cdt = dtype_of(cfg.compute_dtype)
    wx = jnp.einsum("bsd,dhgk->bshgk", u.astype(cdt),
                    params["w_in"].astype(cdt)).astype(jnp.float32)
    state0 = {k: jnp.zeros((B, nh, dh), jnp.float32) for k in ("c", "n", "h")}
    state0["m"] = jnp.full((B, nh, dh), -1e30, jnp.float32)
    r = params["r"].astype(jnp.float32)
    bias = params["bias"].astype(jnp.float32)

    def step(state, wx_t):
        new = _slstm_cell(r, bias, wx_t, state)
        return new, new["h"]

    state, hs = jax.lax.scan(step, state0, wx.swapaxes(0, 1))
    h = hs.swapaxes(0, 1).reshape(B, S, d)
    h = rms_norm(h, params["norm_scale"], cfg.norm_eps)
    out = jnp.einsum("bsd,de->bse", h.astype(cdt), params["out_proj"].astype(cdt))
    return out, state
