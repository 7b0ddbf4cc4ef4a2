"""Serving engine: continuous-batching runtime over a paged KV cache.

Two runtimes share one engine:

* ``continuous`` (default) — a slot-based scheduler admits requests into
  decode slots *as they free up mid-generation* (``repro.serve.scheduler``:
  fifo | sjf | interleave — the tuned ``schedule`` knob acts here), backed
  by either dense per-slot KV buffers or a real paged allocator
  (``repro.serve.paging``; ``kv_cache_pages`` bounds how many requests can
  be resident, which is the memory/throughput trade-off the tuner
  explores).  Decode is one batched dispatch per step at per-slot cache
  lengths; admission-time prefill reuses the exact chunked-prefill path,
  so generated tokens are identical to the wave runtime's and identical
  across schedules (slot math is row-independent).  Two further tuned
  mechanisms ride this runtime without touching tokens: *prefix sharing*
  (``share_prefix``; admission maps registry-matched prompt-prefix page
  groups copy-on-write instead of re-prefilling them) and
  *self-speculative decoding* (``draft_len``; n-gram drafts from the
  request's own history verified as extra columns of the same batched
  dispatch, accepted only where they match what single-token decode
  would have sampled).
* ``wave`` — the legacy static loop (equal-length prompts packed into
  ``batch_slots``-sized waves), kept as the exact-parity fallback and the
  only runtime for stacks without ``supports_continuous_batching``
  (sliding-window rings, recurrent mixers).

The decode step is the same ``serve_step`` the dry-run lowers for the
decode_32k / long_500k cells.
"""
from __future__ import annotations

import functools
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.dist.sharding import RULE_PRESETS, axis_rules
from repro.models import Model

from .paging import (PAGE_TOKENS, OversubscriptionError, PageAllocator,
                     PrefixIndex, min_pages_for)
from .scheduler import (PAGE_POLICIES, SCHEDULES, TP_MODES, Request,
                        SlotScheduler)
from .spans import ACCEPT, ADMIT, DISPATCH, GENERATE, PREFILL, READBACK, span

__all__ = ["ServeConfig", "ServeEngine", "GenerationResult",
           "OversubscriptionError", "TP_MODES"]

RUNTIMES = ("continuous", "wave")
KV_LAYOUTS = ("dense", "paged")


def _tail_history(prompt: Sequence[int], out: List[int],
                  window: int) -> List[int]:
    """The trailing ``window`` tokens of prompt+generated WITHOUT
    materialising the full concatenation — the list build itself was the
    other O(T) term in the per-step drafting cost (``list(prompt) +
    out`` every decode step).  ``window <= 0`` keeps the historical
    unbounded behaviour."""
    if window <= 0:
        return list(prompt) + out
    if window <= len(out):
        return out[-window:]
    head = list(prompt[-(window - len(out)):]) if len(prompt) else []
    return head + out


@dataclass
class ServeConfig:
    max_seq: int = 2048
    batch_slots: int = 8
    temperature: float = 0.0  # 0 = greedy
    eos_token: Optional[int] = None
    seed: int = 0
    # Tunable serving knobs (see repro.serve.space.serve_knob_space; the
    # joint co-tuning mode persists winners for them).  prefill_chunk is
    # the prefill split size: prompts longer than this are prefilled in
    # chunk-sized segments threaded through the KV cache (scheduler
    # granularity vs per-chunk dispatch overhead — under the continuous
    # runtime it is also the interleave quantum).  Models whose blocks
    # cannot append multi-token segments exactly (sliding-window rings,
    # recurrent mixers; see Model.supports_chunked_prefill) prefill whole
    # prompts regardless.
    prefill_chunk: int = 512
    # KV capacity in PAGE_TOKENS-token pages.  Under the paged layout this
    # is a REAL pool: requests reserve page groups at admission and release
    # them at completion, so fewer pages = fewer resident requests.  Under
    # the dense layout (and the wave runtime) batch_slots*max_seq must fit
    # (the buffers really are that big).  None auto-sizes to that footprint
    # (+ the scratch group under paging), so configs that never touch the
    # knob keep working at any max_seq/batch_slots combination.
    kv_cache_pages: Optional[int] = None
    # Admission order under the continuous runtime: fifo | sjf (shortest
    # prompt first) | interleave (fifo admission, prefill chunks issued
    # between decode steps).  The wave runtime runs fifo regardless.
    schedule: str = "fifo"
    # KV reservation policy under the paged layout (repro.serve.scheduler
    # PAGE_POLICIES; a tuned knob — serve_knob_space exposes it):
    #   reserve   — admission reserves the worst-case prompt+max_new
    #               footprint; no preemption, but short generations strand
    #               the unused reservation tail.
    #   on_demand — admission reserves the prompt only; decode grows the
    #               reservation group-by-group and, when the pool runs
    #               dry, preempts the youngest request (recompute: it is
    #               re-queued at the head and re-prefilled with its
    #               generated tokens folded into the prompt — tokens stay
    #               bit-identical because sampling keys on
    #               (rid, token-index)).
    # Dense layouts have no allocator, so the knob is inert there.
    page_policy: str = "reserve"
    # Runtime: continuous batching (slot-level admission) or the legacy
    # equal-length wave loop.  Stacks without supports_continuous_batching
    # fall back to wave automatically.
    runtime: str = "continuous"
    # KV layout under the continuous runtime: dense per-slot buffers or
    # the paged pool + allocator.  The wave runtime is always dense.
    kv_layout: str = "dense"
    # Pages per allocation group == the paged kernel's pages_per_block
    # tile.  With autotune_kernels the tuned paged_attention entry
    # overrides this (clamped so one max_seq request still fits).
    kv_page_block: int = 1
    # Prefix sharing across concurrent requests (paged layout only; a
    # tuned knob): admission content-matches the prompt against a
    # registry of resident fully-prefilled prompt chunks and maps the
    # matched page groups copy-on-write instead of re-prefilling them —
    # TTFT drops by exactly the prefill no longer issued, and the pool
    # hosts more requests because shared groups are stored once.  Tokens
    # are untouched: matching compares token content exactly and chunked
    # prefill is chunk-split-invariant, so shared KV is bitwise the KV
    # the sharer would have computed.  Inert under dense/wave layouts
    # and for requests carrying frontend embeddings (their KV depends on
    # the embeds, not just the token ids).
    share_prefix: bool = False
    # Self-speculative decoding draft length (0 = off; a tuned knob):
    # each decode dispatch carries up to draft_len extra tokens drafted
    # by n-gram lookup in the request's own history, verified as extra
    # columns of the same batched step.  The longest draft prefix that
    # matches what single-token decode would have sampled is accepted —
    # same (rid, token-index) sampling keys, so generated tokens stay
    # bit-identical at any draft_len; only the dispatch count drops.
    draft_len: int = 0
    # n-gram draft lookback bound: only the trailing draft_window tokens
    # of prompt+generated are scanned per draft, so host-side drafting
    # cost stays flat in generation length (it used to rescan the whole
    # history — O(T^2) over a generation).  Tokens never depend on it:
    # a truncated match only changes WHAT gets drafted, and verification
    # accepts exactly what single-token decode would have sampled.
    draft_window: int = 256
    # Effective admission cap <= batch_slots (None = all slots).  The
    # online retuner's max_batch knob acts here: physical slot/dispatch
    # shapes are compiled once, so capping ADMISSION is how max_batch
    # swaps mid-run without draining or recompiling the engine.
    slot_cap: Optional[int] = None
    # Online workload-aware retuning (continuous runtime): fingerprint
    # the live request window (repro.serve.workload), detect drift from
    # the signature the deployed knobs were tuned under, and warm-start
    # a retune whose winner swaps into the running loop at the next step
    # boundary.  All trigger arithmetic counts decode steps (never
    # wall-clock), so the retune step is deterministic per trace.
    retune: bool = False
    retune_budget: int = 16       # SUT tests per retune
    retune_threshold: float = 0.25  # fingerprint distance that triggers
    retune_window: int = 16       # admissions the fingerprint averages
    retune_cooldown: int = 32     # min decode steps between retunes
    retune_check_every: int = 4   # shift-check cadence in decode steps
    retune_min_requests: int = 6  # admissions before fingerprints count
    # the workload signature (fingerprint_sig string) the deployed knobs
    # were tuned under; None anchors on the first full window instead
    tuned_signature: Optional[str] = None
    # Tune/load Pallas block configs for this engine's decode shapes before
    # serving (persisted in the repro.autotune cache, so the compile-time
    # cost is paid once per (shape, dtype, backend)).
    autotune_kernels: bool = False
    autotune_budget: int = 12
    # Multi-device serving: a (data, model) mesh shape (None = single
    # device).  The ``model`` axis is the tensor-parallel split — heads /
    # ff / vocab columns (and the paged pool's KV-head axis) shard across
    # it and every decode step all-reduces partial sums.  The ``data``
    # axis carries engine REPLICAS: batch slots spread over it and the
    # engine widens slot/page capacity ×data, so the config's
    # batch_slots / kv_cache_pages stay per-replica quantities.  Both
    # layouts (and the meshes between) are tuned knobs —
    # ``serve_knob_space(max_devices=...)`` exposes ``mesh_devices`` /
    # ``tp_vs_replicas`` and the joint mode co-tunes them with schedule,
    # page policy and kernel blocks.
    mesh_shape: Optional[Tuple[int, int]] = None
    # AxisRules preset (repro.dist.sharding.RULE_PRESETS) activated for
    # sharded generation.  "serve_tp" is safe for every mesh shape: on a
    # (K, 1) replicas mesh its model-axis mappings drop (size-1 axis) and
    # it degenerates to "serve_replicas" exactly.
    rules_preset: str = "serve_tp"
    # Which mesh orientation a flat tuned device count maps to (TP_MODES;
    # ``apply_serve_knobs`` writes the resolved mesh_shape from it) —
    # recorded here so deployed configs carry the tuner's choice.
    tp_vs_replicas: str = "tp"

    def __post_init__(self):
        if self.schedule not in SCHEDULES:
            raise ValueError(f"unknown schedule {self.schedule!r}; "
                             f"have {SCHEDULES}")
        if self.runtime not in RUNTIMES:
            raise ValueError(f"unknown runtime {self.runtime!r}; "
                             f"have {RUNTIMES}")
        if self.kv_layout not in KV_LAYOUTS:
            raise ValueError(f"unknown kv_layout {self.kv_layout!r}; "
                             f"have {KV_LAYOUTS}")
        if self.page_policy not in PAGE_POLICIES:
            raise ValueError(f"unknown page_policy {self.page_policy!r}; "
                             f"have {PAGE_POLICIES}")
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if self.kv_page_block < 1:
            raise ValueError("kv_page_block must be >= 1")
        if self.draft_len < 0:
            raise ValueError("draft_len must be >= 0")
        if self.draft_window < 2:
            raise ValueError("draft_window must be >= 2 (an n-gram draft "
                             "needs at least a 1-token suffix + 1 earlier "
                             "token to match against)")
        if self.slot_cap is not None and not (
                1 <= self.slot_cap <= self.batch_slots):
            raise ValueError(f"slot_cap must be in [1, batch_slots="
                             f"{self.batch_slots}]; got {self.slot_cap}")
        for knob in ("retune_budget", "retune_window", "retune_cooldown",
                     "retune_check_every", "retune_min_requests"):
            if getattr(self, knob) < 1:
                raise ValueError(f"{knob} must be >= 1")
        if self.retune_threshold < 0:
            raise ValueError("retune_threshold must be >= 0")
        if self.rules_preset not in RULE_PRESETS:
            raise ValueError(f"unknown rules_preset {self.rules_preset!r}; "
                             f"have {sorted(RULE_PRESETS)}")
        if self.tp_vs_replicas not in TP_MODES:
            raise ValueError(f"unknown tp_vs_replicas "
                             f"{self.tp_vs_replicas!r}; have {TP_MODES}")
        if self.mesh_shape is not None:
            ms = tuple(int(x) for x in self.mesh_shape)
            if len(ms) != 2 or any(x < 1 for x in ms):
                raise ValueError(f"mesh_shape must be a (data, model) pair "
                                 f"of positive ints; got {self.mesh_shape!r}")
            self.mesh_shape = ms
        paged = self.runtime == "continuous" and self.kv_layout == "paged"
        needed = self.batch_slots * self.max_seq
        # remember auto-sizing: the engine re-derives a full-residency pool
        # if autotuning later changes the group size (pages_per_block)
        self._kv_pages_auto = self.kv_cache_pages is None
        if self.kv_cache_pages is None:
            pages = -(-needed // PAGE_TOKENS)
            if paged:  # round to group granularity + the scratch group
                ppb = self.kv_page_block
                pages = (-(-pages // ppb) + 1) * ppb
            self.kv_cache_pages = pages
        if paged:
            # Pages bound residency, not the dense footprint — but one
            # max_seq request (plus the scratch group) must always fit.
            floor = min_pages_for(self.max_seq, self.kv_page_block)
            if self.kv_cache_pages < floor:
                raise ValueError(
                    f"KV cache too small: a single {self.max_seq}-token "
                    f"request (+ the scratch group) needs {floor} pages at "
                    f"{self.kv_page_block} pages/group but "
                    f"kv_cache_pages={self.kv_cache_pages}")
        else:
            capacity = self.kv_cache_pages * PAGE_TOKENS
            if needed > capacity:
                raise ValueError(
                    f"KV cache too small: {self.batch_slots} slots x "
                    f"{self.max_seq} tokens needs {needed} tokens but "
                    f"kv_cache_pages={self.kv_cache_pages} holds only "
                    f"{capacity}")


@dataclass
class GenerationResult:
    tokens: List[List[int]]  # generated continuations (per request)
    prefill_seconds: float
    decode_seconds: float
    steps: int  # batched decode dispatches
    # prefill dispatches actually issued (> waves when chunked prefill
    # split prompts; per-slot under the continuous runtime) — the
    # observable evidence the prefill_chunk knob acts
    prefill_chunks: int = 0
    # per-request runtime provenance (rid order == input order):
    # {"rid", "prompt_len", "new_tokens", "admit_s", "latency_s",
    #  "ttft_s", "preemptions"}; the times are seconds from the call's
    # start: first admission to a slot (a preempted request keeps it),
    # first token, last token
    per_request: List[Dict[str, Any]] = field(default_factory=list)
    # recompute preemptions issued (on_demand page policy only): each one
    # re-queued a request whose re-prefill cost is the price of admitting
    # on prompt-size reservations instead of worst-case ones
    preemptions: int = 0
    # prefix-sharing + speculative-decoding provenance: prompt tokens
    # admitted straight from shared resident groups (their prefill was
    # skipped), copy-on-write group splits performed, draft tokens
    # proposed to verification, and draft tokens accepted (beyond the
    # guaranteed first token of every dispatch)
    shared_prefix_tokens: int = 0
    cow_splits: int = 0
    drafted: int = 0
    accepted: int = 0
    # online retune events (cfg.retune): one dict per swap — {"step",
    # "distance", "signature", "fingerprint", "config", "value",
    # "n_tests", "warm_source", "spec_accept", "measured_accept",
    # "applied": {knob: (old, new)}}
    retunes: List[Dict[str, Any]] = field(default_factory=list)
    # decode steps before which admission stopped with a free slot and a
    # pending request because the pool could not hold the head's
    # reservation (paged runtime; 0 where the pool never refused)
    admission_blocked: int = 0

    @property
    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens that verification accepted.

        ``nan`` when nothing was drafted: "no speculation ran" and "every
        draft was rejected" are different facts, and the old 0.0-for-both
        answer poisoned any feedback loop that treated it as a measured
        rate (the online retuner would have pinned ``spec_accept`` to 0
        on runs that simply had ``draft_len=0``).  Consumers must guard
        with ``math.isnan`` before feeding it anywhere numeric."""
        if self.drafted == 0:
            return float("nan")
        return self.accepted / self.drafted

    @property
    def decode_tokens_per_sec(self) -> float:
        """Tokens of decode steps per decode second.  The first token of
        every admission (a request's, and a preempted request's again on
        readmission) comes from its prefill's logits, not a decode step."""
        n = sum(len(t) for t in self.tokens) - len(self.tokens) \
            - self.preemptions
        return n / max(self.decode_seconds, 1e-9)

    def latency_percentile(self, q: float) -> float:
        """q-th percentile (0..100) of per-request latency seconds."""
        lats = [r["latency_s"] for r in self.per_request]
        if not lats:
            return 0.0
        return float(np.percentile(np.asarray(lats), q))

    @property
    def p50_latency_s(self) -> float:
        return self.latency_percentile(50.0)

    @property
    def p95_latency_s(self) -> float:
        return self.latency_percentile(95.0)


class ServeEngine:
    def __init__(self, model: Model, params, cfg: ServeConfig):
        import dataclasses

        self.model = model
        self.params = params
        # private copy: pool sizing below rewrites kv_cache_pages (group
        # rounding, wave-fallback footprint, autotuned group size) and
        # must not leak into a caller-owned config reused across engines
        orig = cfg
        self.cfg = cfg = dataclasses.replace(cfg)
        cfg._kv_pages_auto = getattr(orig, "_kv_pages_auto", False)
        # --- mesh resolution: the (data, model) device grid ------------
        self.mesh = None
        self.rules = RULE_PRESETS[cfg.rules_preset]
        data, tp = cfg.mesh_shape or (1, 1)
        self.mesh_shape = (data, tp)
        if data * tp > 1:
            n_dev = len(jax.devices())
            if n_dev % (data * tp):
                raise ValueError(
                    f"mesh_shape {self.mesh_shape} needs {data * tp} "
                    f"devices evenly out of {n_dev} available")
            from repro.launch.mesh import make_mesh

            self.mesh = make_mesh(data, tp)
            if data > 1:
                # replicas widening: the config's capacity knobs are
                # per-data-slice, so the flat engine runs data× of them
                # (each replica's slots/pool shard onto its own slice)
                cfg.batch_slots *= data
                if cfg.slot_cap is not None:
                    cfg.slot_cap *= data
                if not cfg._kv_pages_auto:
                    cfg.kv_cache_pages *= data
                elif cfg.kv_layout != "paged":
                    # auto dense footprint: re-derive at the widened slots
                    cfg.kv_cache_pages = -(-cfg.batch_slots * cfg.max_seq
                                           // PAGE_TOKENS)
        self._continuous = (cfg.runtime == "continuous"
                            and model.supports_continuous_batching)
        self._paged = self._continuous and cfg.kv_layout == "paged"
        if cfg.kv_layout == "paged" and not self._paged:
            # A paged config passed the lenient one-request validation,
            # but this stack runs dense buffers (wave fallback): restore
            # the dense footprint accounting the paged branch waived, so
            # the config honestly reports the memory actually allocated.
            needed = cfg.batch_slots * cfg.max_seq
            if cfg.kv_cache_pages * PAGE_TOKENS < needed:
                cfg.kv_cache_pages = -(-needed // PAGE_TOKENS)
        # tuned block configs for this engine's kernel shapes (filled when
        # cfg.autotune_kernels; consulted implicitly by repro.kernels.ops)
        self.kernel_blocks: Dict[str, Dict[str, Any]] = {}
        mcfg = model.cfg
        if cfg.autotune_kernels:
            # the decode cache buffer is always max_seq long; prompt-length
            # dependent shapes are warmed lazily per wave in generate()
            self.kernel_blocks["decode_attention"] = self._ensure(
                "decode_attention",
                {"B": cfg.batch_slots, "S": cfg.max_seq,
                 "H": mcfg.padded_heads, "KV": mcfg.n_kv_heads,
                 "D": mcfg.head_dim_})
        if self._paged:
            self._size_paged_pool()
        if self.mesh is not None:
            # lay the weights out per the rule table up front: heads/ff
            # columns land on their model-axis shard once, and every jit
            # below traces against committed sharded inputs
            self.params = self._shard_tree(
                self.params, model.param_specs(self.rules, self.mesh))
        jit = jax.jit if self.mesh is None else self._jit_mesh_keyed
        self._prefill = jit(model.prefill)
        self._prefill_chunk = jit(model.prefill_chunk)
        self._decode = jit(model.decode_step)
        if self._continuous:
            self._decode_multi = jit(model.decode_step_multi)
            self._slot_chunk = jit(model.prefill_chunk_slot)
            self._slot_chunk_paged = jit(model.prefill_chunk_slot_paged)
            self._argmax_multi = jax.jit(self._greedy_rows)
            self._categorical_multi = jax.jit(self._categorical_rows)
            self._argmax_grid = jax.jit(self._greedy_grid)
            self._categorical_grid_j = jax.jit(self._categorical_grid)
            self._copy_group = jax.jit(self._copy_group_blocks)

    # ------------------------------------------------------------------
    def _ensure(self, kernel: str, dims: Dict[str, int]) -> Dict[str, Any]:
        from repro import autotune

        return autotune.ensure_tuned(kernel, dims,
                                     dtype=self.model.cfg.compute_dtype,
                                     budget=self.cfg.autotune_budget)

    def _shard_tree(self, tree, specs):
        """device_put a pytree onto the mesh with per-leaf NamedShardings.

        ``specs`` mirrors ``tree`` with a PartitionSpec at every array
        position; the spec tree is flattened only UP TO the data tree's
        structure, so each spec stays one leaf whatever its type."""
        flat, treedef = jax.tree_util.tree_flatten(tree)
        sflat = treedef.flatten_up_to(specs)
        put = [jax.device_put(x, NamedSharding(self.mesh,
                                               PartitionSpec(*s)))
               for x, s in zip(flat, sflat)]
        return jax.tree_util.tree_unflatten(treedef, put)

    def _jit_mesh_keyed(self, fn):
        """``jax.jit`` with the trace cache keyed to THIS engine.

        Bound methods of a shared ``Model`` hash equal across engines, and
        jax's jaxpr-tracing cache does not see the ambient mesh that
        ``constrain`` captures at trace time — so two sharded engines over
        the same model with coinciding avals (e.g. a (2,1) and a (2,2)
        mesh both widen slots x2) would hand each other jaxprs whose
        sharding constraints pin the OTHER engine's devices.  A per-engine
        closure (identity-hashed) makes the reuse impossible."""
        @functools.wraps(fn)
        def keyed(*args, **kwargs):
            return fn(*args, **kwargs)
        return jax.jit(keyed)

    def _sharding_ctx(self):
        """The ``axis_rules`` context generation runs under: tracing the
        jitted steps inside it attaches ``constrain`` activation
        constraints for this engine's rule table + mesh.  Single-device
        engines get a no-op context — same code path, unsharded."""
        if self.mesh is None:
            return nullcontext()
        return axis_rules(self.rules, self.mesh)

    def _size_paged_pool(self) -> None:
        """Fix the pool geometry: group size (pages), groups per request,
        total groups.  With autotune the paged kernel's tuned
        ``pages_per_block`` becomes the group size — clamped so one
        max_seq request still fits the configured page budget — and the
        winner is re-keyed under the runtime pool signature so the
        ``ops.paged_flash_decode`` consult point hits it."""
        cfg, mcfg = self.cfg, self.model.cfg
        ppb = cfg.kv_page_block
        if cfg.autotune_kernels:
            tuned = self._ensure(
                "paged_attention",
                {"B": cfg.batch_slots, "S": cfg.max_seq,
                 "H": mcfg.padded_heads, "KV": mcfg.n_kv_heads,
                 "D": mcfg.head_dim_})
            self.kernel_blocks["paged_attention"] = tuned
            ppb = int(tuned.get("pages_per_block", ppb))
        if not getattr(cfg, "_kv_pages_auto", False):
            while ppb > 1:  # tuned tile too coarse for this page budget
                if cfg.kv_cache_pages >= min_pages_for(cfg.max_seq, ppb):
                    break
                ppb //= 2
        self.group_pages = ppb
        self.group_tokens = ppb * PAGE_TOKENS
        self.max_groups = -(-cfg.max_seq // self.group_tokens)
        if getattr(cfg, "_kv_pages_auto", False):
            # auto-sized budget: full residency at the adopted group size
            self.pool_groups = cfg.batch_slots * self.max_groups + 1
        else:
            self.pool_groups = max(cfg.kv_cache_pages // ppb,
                                   self.max_groups + 1)
        # the config reports the pool actually allocated (group rounding,
        # one-request minimum and auto-resizing can all move it)
        cfg.kv_cache_pages = self.pool_groups * ppb
        if cfg.autotune_kernels:
            self._rekey_paged_entry()

    def _rekey_paged_entry(self) -> None:
        """Persist the paged winner under the dims the pool actually runs
        (S = max_groups * group_tokens), so the runtime consult point in
        ``ops.paged_flash_decode`` resolves the tuned launch knobs."""
        from repro import autotune

        mcfg = self.model.cfg
        logical = {"B": self.cfg.batch_slots, "S": self.cfg.max_seq,
                   "H": mcfg.padded_heads, "KV": mcfg.n_kv_heads,
                   "D": mcfg.head_dim_}
        runtime = dict(logical, S=self.max_groups * self.group_tokens)
        if runtime == logical:
            return
        cache = autotune.default_cache()
        entry = cache.get("paged_attention", autotune.shape_sig(logical),
                          mcfg.compute_dtype, autotune.backend_name())
        if not entry:
            return
        # rebuild-per-trial loops (LiveServeSUT) construct many engines:
        # skip the full-file cache rewrite when the entry already landed
        existing = cache.get_config("paged_attention",
                                    autotune.shape_sig(runtime),
                                    mcfg.compute_dtype,
                                    autotune.backend_name())
        if existing == entry["config"]:
            return
        cache.put("paged_attention", autotune.shape_sig(runtime),
                  mcfg.compute_dtype, autotune.backend_name(),
                  entry["config"], entry["value"],
                  meta=dict(entry.get("meta", {}), rekeyed_from="logical"))

    def _warm_prefill_blocks(self, prompt_len: int) -> None:
        """Tune/load block configs for the shapes this wave actually runs:
        prefill attention at S=prompt_len, rmsnorm at the prefill and
        decode row counts.  Idempotent per shape (cache hits are free)."""
        mcfg = self.model.cfg
        B = self.cfg.batch_slots
        self.kernel_blocks["flash_attention"] = self._ensure(
            "flash_attention",
            {"B": B, "S": prompt_len, "SK": prompt_len,
             "H": mcfg.padded_heads, "KV": mcfg.n_kv_heads,
             "D": mcfg.head_dim_})
        self.kernel_blocks["rmsnorm_prefill"] = self._ensure(
            "rmsnorm", {"ROWS": B * prompt_len, "D": mcfg.d_model})
        self.kernel_blocks["rmsnorm_decode"] = self._ensure(
            "rmsnorm", {"ROWS": B, "D": mcfg.d_model})

    def _make_retuner(self):
        """The online workload-aware retuner for this engine (cfg.retune).

        The retuner optimises over the same ``serve_knob_space`` the
        offline joint mode tunes — with ``kv_cache_pages`` frozen to the
        pool actually allocated (the device pool is compiled; resizing it
        mid-run would recompile) — and keys its cache entries by the
        SAME shape signature ``launch/tune.py`` uses, so online winners
        and offline joint-tune winners transfer both ways through
        nearest-signature lookup."""
        from repro.autotune import mesh_sig

        from .space import CotuneParams, serve_knob_space
        from .workload import OnlineRetuner

        cfg, mcfg = self.cfg, self.model.cfg
        B = cfg.batch_slots
        base_params = CotuneParams.from_model(mcfg, max_seq=cfg.max_seq)
        # clamp the allocated pool into the knob's range (the space uses
        # the same page_per_seq arithmetic as serve_knob_space) so the
        # frozen value always validates
        lo = max(1, cfg.max_seq // PAGE_TOKENS)
        pages = min(max(cfg.kv_cache_pages, lo), B * lo)
        space = serve_knob_space(cfg.max_seq, max_slots=B).freeze(
            {"kv_cache_pages": pages})
        active = {
            "max_batch": min(cfg.slot_cap or B, B),
            "prefill_chunk": cfg.prefill_chunk,
            "kv_cache_pages": pages,
            "schedule": cfg.schedule,
            "page_policy": cfg.page_policy,
            "share_prefix": int(bool(cfg.share_prefix)),
            "draft_len": cfg.draft_len,
        }
        # the exact dims launch/tune.py keys serve winners under
        sig_dims = {"S": cfg.max_seq, "H": mcfg.padded_heads,
                    "KV": mcfg.n_kv_heads, "D": mcfg.head_dim_}
        return OnlineRetuner(
            space, base_params, baseline=cfg.tuned_signature,
            budget=cfg.retune_budget, threshold=cfg.retune_threshold,
            min_requests=cfg.retune_min_requests,
            cooldown=cfg.retune_cooldown,
            check_every=cfg.retune_check_every, seed=cfg.seed,
            active_config=active, sig_dims=sig_dims,
            dtype=mcfg.compute_dtype,
            # winners persist/resolve at THIS engine's topology only
            # (schema v4 keys by mesh signature)
            mesh=mesh_sig(self.mesh_shape))

    # ------------------------------------------------------------------
    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: Union[int, Sequence[int]],
        frontend_embeds: Optional[np.ndarray] = None,
    ) -> GenerationResult:
        """Generate continuations for a batch of requests.

        Under the continuous runtime prompts may have MIXED lengths and
        ``max_new_tokens`` may be per-request; completed requests free
        their slot (and KV pages) for pending ones mid-generation.  The
        wave runtime keeps the historical contract: equal-length prompts
        packed into ``batch_slots``-sized waves, short final wave padded
        with dummies.
        """
        mcfg = self.model.cfg
        if (mcfg.frontend or mcfg.encoder) and frontend_embeds is None:
            # Fail loudly on BOTH prefill paths: the whole-prompt path
            # would KeyError deep in _memory, and the chunked path would
            # silently attend to the cache's zero-initialized memory.
            raise ValueError(
                f"{mcfg.name} has a modality frontend/encoder; generate() "
                "requires frontend_embeds")
        n = len(prompts)
        if isinstance(max_new_tokens, (int, np.integer)):
            max_new = [int(max_new_tokens)] * n
        else:
            max_new = [int(m) for m in max_new_tokens]
            if len(max_new) != n:
                raise ValueError("per-request max_new_tokens length must "
                                 "match the number of prompts")
        if any(m < 1 for m in max_new):
            raise ValueError("max_new_tokens must be >= 1")
        for p, m in zip(prompts, max_new):
            if len(p) + m > self.cfg.max_seq:
                raise ValueError("prompt + generation exceeds max_seq")
        with span(GENERATE, requests=n), self._sharding_ctx():
            if self._continuous:
                return self._generate_continuous(prompts, max_new,
                                                 frontend_embeds)
            return self._generate_waves(prompts, max_new, frontend_embeds)

    # ------------------------------------------------------------------
    # wave runtime (legacy exact-parity path)
    # ------------------------------------------------------------------
    def _generate_waves(self, prompts, max_new: List[int],
                        frontend_embeds) -> GenerationResult:
        lens = {len(p) for p in prompts}
        if len(lens) != 1:
            raise ValueError("the wave runtime batches equal-length "
                             f"prompts; got lengths {sorted(lens)} "
                             "(use runtime='continuous' for mixed)")
        (plen,) = lens
        if self.cfg.autotune_kernels:
            self._warm_prefill_blocks(plen)

        slots = self.cfg.batch_slots
        outputs: List[List[int]] = []
        per_request: List[Dict[str, Any]] = []
        prefill_s = decode_s = 0.0
        steps = chunks = 0
        t0 = time.time()
        for wave_start in range(0, len(prompts), slots):
            wave = list(prompts[wave_start:wave_start + slots])
            wave_new = max_new[wave_start:wave_start + slots]
            n_real = len(wave)
            while len(wave) < slots:
                wave.append(wave[0])  # pad with a copy; discarded later
            fe = None
            if frontend_embeds is not None:
                fe = frontend_embeds[wave_start:wave_start + slots]
                if fe.shape[0] < slots:
                    reps = np.repeat(fe[:1], slots - fe.shape[0], axis=0)
                    fe = np.concatenate([fe, reps], axis=0)
            admit_s = time.time() - t0
            toks, pf, dc, st, nc = self._generate_wave(
                np.asarray(wave, np.int32), max(wave_new), fe)
            wave_done = time.time() - t0
            for i in range(n_real):
                t = toks[i][:wave_new[i]]
                outputs.append(t)
                per_request.append({
                    "rid": wave_start + i, "prompt_len": plen,
                    "new_tokens": len(t),
                    "admit_s": admit_s,
                    "latency_s": wave_done,
                    "ttft_s": wave_done - dc,
                    "preemptions": 0,  # waves hold slots to completion
                })
            prefill_s += pf
            decode_s += dc
            steps += st
            chunks += nc
        return GenerationResult(outputs, prefill_s, decode_s, steps, chunks,
                                per_request)

    def _generate_wave(self, prompt_arr: np.ndarray, max_new: int,
                       frontend_embeds) -> Any:
        B, P = prompt_arr.shape
        cache = self.model.init_cache(B, max_seq=self.cfg.max_seq)

        chunk = self.cfg.prefill_chunk
        chunked = chunk < P and self.model.supports_chunked_prefill
        # host->device conversion stays OUTSIDE the timed window, so
        # prefill_seconds keeps measuring model time like it always has
        tokens = jnp.asarray(prompt_arr)
        fe = jnp.asarray(frontend_embeds) \
            if frontend_embeds is not None else None
        t0 = time.time()
        if chunked:
            # Chunked prefill: run the prompt through the model in
            # chunk-sized segments, threading the KV cache between calls.
            # Exact (same tokens, same cache) as whole-prompt prefill for
            # the block kinds that support it; the knob trades scheduler
            # granularity against per-chunk dispatch overhead.
            n_chunks = 0
            for start in range(0, P, chunk):
                piece = {"tokens": tokens[:, start:start + chunk]}
                if start == 0 and fe is not None:
                    piece["frontend_embeds"] = fe
                logits, cache = self._prefill_chunk(self.params, piece,
                                                    cache)
                n_chunks += 1
        else:
            batch = {"tokens": tokens}
            if fe is not None:
                batch["frontend_embeds"] = fe
            logits, cache = self._prefill(self.params, batch, cache)
            n_chunks = 1
        logits.block_until_ready()
        prefill_s = time.time() - t0

        rng = jax.random.PRNGKey(self.cfg.seed)
        out = np.zeros((B, max_new), np.int64)
        done = np.zeros(B, bool)
        t0 = time.time()
        produced = 0
        for step in range(max_new):
            tok = self._sample(logits, rng, step)
            out[:, step] = np.asarray(tok[:, 0])
            produced = step + 1
            if self.cfg.eos_token is not None:
                done |= out[:, step] == self.cfg.eos_token
                if done.all():
                    break
            if produced < max_new:
                logits, cache = self._decode(self.params, tok, cache)
        decode_s = time.time() - t0

        results = []
        for b in range(B):
            toks = out[b, :produced].tolist()
            if self.cfg.eos_token is not None and self.cfg.eos_token in toks:
                toks = toks[:toks.index(self.cfg.eos_token) + 1]
            results.append(toks)
        return results, prefill_s, decode_s, produced, n_chunks

    def _sample(self, logits, rng, step):
        lg = logits[:, -1, :self.model.cfg.vocab_size].astype(jnp.float32)
        if self.cfg.temperature <= 0:
            return jnp.argmax(lg, axis=-1, keepdims=True).astype(jnp.int32)
        key = jax.random.fold_in(rng, step)
        return jax.random.categorical(
            key, lg / self.cfg.temperature, axis=-1)[:, None].astype(jnp.int32)

    # ------------------------------------------------------------------
    # continuous-batching runtime
    # ------------------------------------------------------------------
    def _greedy_rows(self, logits):
        lg = logits[:, -1, :self.model.cfg.vocab_size].astype(jnp.float32)
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)

    def _base_key(self, rid: int):
        """Per-request PRNG root.  Token ``i`` of request ``rid`` is always
        sampled with ``fold_in(_base_key(rid), i)`` — BOTH the prefill-tail
        path (``_sample_slot``) and the batched decode path
        (``_categorical_rows``) compose keys this way, which is what makes
        temperature sampling schedule- and slot-placement-invariant."""
        return jax.random.fold_in(jax.random.PRNGKey(self.cfg.seed), rid)

    def _categorical_rows(self, logits, base_keys, produced):
        """Per-slot keys derive from (request id, token index) only, so
        sampled tokens are schedule- and slot-placement-invariant."""
        lg = logits[:, -1, :self.model.cfg.vocab_size].astype(jnp.float32)
        keys = jax.vmap(jax.random.fold_in)(base_keys, produced)
        return jax.vmap(
            lambda k, row: jax.random.categorical(
                k, row / self.cfg.temperature))(keys, lg).astype(jnp.int32)

    def _greedy_grid(self, logits):
        """Greedy over a (B, C, V) speculative-verify grid -> (B, C)
        tokens; column 0 is exactly ``_greedy_rows`` of the single-token
        dispatch."""
        lg = logits[..., :self.model.cfg.vocab_size].astype(jnp.float32)
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)

    def _categorical_grid(self, logits, base_keys, produced):
        """Temperature sampling over a (B, C, V) verify grid: column i of
        slot b keys on ``fold_in(base_key[b], produced[b] + i)`` — the
        key single-token decode would use i steps later — which is what
        makes speculative acceptance token-parity-exact."""
        lg = logits[..., :self.model.cfg.vocab_size].astype(jnp.float32)
        offs = jnp.arange(lg.shape[1], dtype=jnp.int32)

        def row(base, p0, rows):
            return jax.vmap(lambda i, r: jax.random.categorical(
                jax.random.fold_in(base, p0 + i),
                r / self.cfg.temperature))(offs, rows)

        return jax.vmap(row)(base_keys, produced, lg).astype(jnp.int32)

    def _copy_group_blocks(self, blocks, src, dst):
        """Device copy of one physical pool group (the CoW split): every
        paged cache leaf is (n_sub, G, KV, T, D) — copy pool row
        ``src`` into ``dst`` across all blocks."""
        return jax.tree_util.tree_map(
            lambda l: l.at[:, dst].set(l[:, src]), blocks)

    @staticmethod
    def _ngram_draft(history: List[int], k: int, max_n: int = 3,
                     window: int = 0) -> List[int]:
        """Self-drafted continuation: find the most recent earlier
        occurrence of the longest (<= max_n) suffix of ``history`` and
        propose the <= k tokens that followed it.  Pure host-side
        heuristic — a wrong draft costs wasted verify columns, never
        correctness (verification accepts exactly what single-token
        decode would have produced).

        ``window`` bounds the lookback to the trailing ``window`` tokens
        (0 = unbounded).  The unbounded scan was O(len(history)) per
        decode step — O(T^2) over a generation, a real host-side drag on
        long generations.  Generated tokens can NOT depend on the bound:
        drafts only ever change which verify columns are issued, and
        acceptance compares against what single-token decode samples."""
        if window and len(history) > window:
            history = history[-window:]
        L = len(history)
        if k <= 0 or L < 2:
            return []
        for n in range(min(max_n, L - 1), 0, -1):
            suffix = history[L - n:]
            for s in range(L - n - 1, -1, -1):
                if history[s:s + n] == suffix:
                    return history[s + n:s + n + k]
        return []

    def _init_continuous_cache(self):
        """Slot KV state: dense per-slot buffers or the paged pools, plus
        the per-slot frontend memory buffer (never paged — fixed width)."""
        mcfg = self.model.cfg
        B = self.cfg.batch_slots
        if self._paged:
            cache = self.model.init_paged_cache(self.pool_groups,
                                                self.group_tokens)
            if self.mesh is not None:
                # POOL_AXES: page groups stay whole per device, only the
                # KV-head axis follows the model-axis split
                cache = self._shard_tree(
                    cache, self.model.paged_cache_specs(
                        self.pool_groups, self.group_tokens,
                        self.rules, self.mesh))
            if mcfg.frontend or mcfg.encoder:
                from repro.models.common import dtype_of

                cache["memory"] = jnp.zeros(
                    (B, mcfg.frontend_tokens, mcfg.d_model),
                    dtype_of(mcfg.compute_dtype))
        else:
            cache = self.model.init_cache(B, max_seq=self.cfg.max_seq)
            cache.pop("index", None)  # lengths are per-slot host state
            if self.mesh is not None:
                specs = self.model.cache_specs(B, self.cfg.max_seq,
                                               self.rules, self.mesh)
                specs.pop("index", None)
                cache = self._shard_tree(cache, specs)
        return cache

    def _generate_continuous(self, prompts, max_new: List[int],
                             frontend_embeds) -> GenerationResult:
        t0 = time.time()  # every per-request stamp is taken from here
        cfg = self.cfg
        B = cfg.batch_slots
        reqs = []
        for i, p in enumerate(prompts):
            fe = None
            if frontend_embeds is not None:
                fe = np.asarray(frontend_embeds[i:i + 1])
            reqs.append(Request(i, list(p), max_new[i], fe))
        sched = SlotScheduler(cfg.schedule, B, page_policy=cfg.page_policy)
        sched.submit(reqs)
        alloc = None
        prefix = None
        if self._paged:
            # the allocator mirrors the device pool exactly (pool_groups
            # already folds in the one-request minimum / auto-sizing)
            alloc = PageAllocator(self.pool_groups * self.group_pages,
                                  PAGE_TOKENS, self.group_pages)
            page_tables = np.zeros((B, self.max_groups), np.int32)
            # with retuning the registry is kept warm even while sharing
            # is off, so a mid-run swap to share_prefix=1 has resident
            # prompts to match against (matching itself is gated on the
            # live cfg.share_prefix in shared_match)
            if cfg.share_prefix or cfg.retune:
                prefix = PrefixIndex(alloc)
        # on_demand reservations persist after a mid-run swap back to
        # "reserve": live prompt-only reservations still need the decode
        # extend path until they drain, so the latch only ever sets
        ever_on_demand = alloc is not None and sched.on_demand
        cache = self._init_continuous_cache()
        # admission cap (the retuner's max_batch knob): only slots below
        # the cap admit, so physical dispatch shapes never change
        slot_cap = min(cfg.slot_cap or B, B)
        window = retuner = None
        retunes: List[Dict[str, Any]] = []
        seen_rids: set = set()
        if cfg.retune:
            from .workload import WorkloadWindow

            window = WorkloadWindow(capacity=cfg.retune_window)
            retuner = self._make_retuner()
        self.last_retuner = retuner

        # host-side slot state
        slot_req: List[Optional[Request]] = [None] * B
        slot_chunks: List[List[np.ndarray]] = [[] for _ in range(B)]
        slot_first_chunk = [False] * B  # frontend embeds ride chunk 0
        slot_out: List[List[int]] = [[] for _ in range(B)]
        lengths = np.zeros(B, np.int64)
        next_tok = np.zeros(B, np.int32)
        base_keys = jnp.zeros((B,) + jax.random.PRNGKey(0).shape,
                              jax.random.PRNGKey(0).dtype)

        results: List[Optional[List[int]]] = [None] * len(prompts)
        per_request: List[Optional[Dict[str, Any]]] = [None] * len(prompts)
        first_tok_t: Dict[int, float] = {}  # rid -> first-ever-token time
        admit_t: Dict[int, float] = {}  # rid -> first admission time
        shared_by_rid: Dict[int, int] = {}  # rid -> shared-admitted tokens
        prefill_s = decode_s = 0.0
        steps = chunks_issued = preemptions = admission_blocked = 0
        shared_total = cow_splits = drafted = accepted = 0

        def run_chunk(b: int) -> None:
            nonlocal cache, prefill_s, chunks_issued
            piece_tokens = slot_chunks[b].pop(0)
            r = slot_req[b]
            last = not slot_chunks[b]
            with span(PREFILL, rid=r.rid, slot=b,
                      tokens=piece_tokens.shape[1], last=int(last)):
                piece = {"tokens": jnp.asarray(piece_tokens)}
                if slot_first_chunk[b]:
                    slot_first_chunk[b] = False
                    if r.frontend_embeds is not None:
                        piece["frontend_embeds"] = jnp.asarray(
                            r.frontend_embeds)
                t = time.time()
                if self._paged:
                    logits, new_cache = self._slot_chunk_paged(
                        self.params, piece, cache,
                        jnp.asarray(page_tables[b]),
                        jnp.asarray(lengths[b], jnp.int32),
                        jnp.asarray(b, jnp.int32))
                else:
                    logits, new_cache = self._slot_chunk(
                        self.params, piece, cache, jnp.asarray(b, jnp.int32),
                        jnp.asarray(lengths[b], jnp.int32))
                cache = new_cache
                lengths[b] += piece_tokens.shape[1]
                chunks_issued += 1
                if not last:
                    logits.block_until_ready()
                    prefill_s += time.time() - t
                    return
                # prefill done: publish this prompt's full-chunk groups
                # for sharers; frontend requests never register — their
                # KV depends on the embeds, not just the token ids, so
                # content-matched sharing would alias different
                # activations
                if prefix is not None and r.frontend_embeds is None:
                    prefix.register(list(r.prompt),
                                    [int(g) for g in page_tables[b]])
                # sample the next token; token index = tokens already
                # carried from before a preemption (0 for fresh requests)
                # — the (rid, index) sampling key continues exactly where
                # it left off
                tok = int(np.asarray(self._sample_slot(
                    logits, r.rid, len(slot_out[b]))))
                prefill_s += time.time() - t
                first_tok_t.setdefault(r.rid, time.time())
            accept_token(b, tok)

        def accept_token(b: int, tok: int) -> None:
            r = slot_req[b]
            slot_out[b].append(tok)
            next_tok[b] = tok
            done = len(slot_out[b]) >= r.max_new or (
                cfg.eos_token is not None and tok == cfg.eos_token)
            if done:
                finish_slot(b)

        def clear_slot(b: int) -> None:
            slot_req[b] = None
            slot_out[b] = []
            slot_chunks[b] = []
            lengths[b] = 0
            next_tok[b] = 0
            if alloc is not None:
                page_tables[b, :] = PageAllocator.SCRATCH_GROUP

        def finish_slot(b: int) -> None:
            r = slot_req[b]
            now = time.time()
            results[r.rid] = list(slot_out[b])
            per_request[r.rid] = {
                "rid": r.rid, "prompt_len": r.prompt_len,
                "new_tokens": len(slot_out[b]),
                "admit_s": admit_t[r.rid] - t0,
                "latency_s": now - t0,
                "ttft_s": first_tok_t.get(r.rid, now) - t0,
                "preemptions": r.preemptions,
                "shared_tokens": shared_by_rid.get(r.rid, 0),
            }
            if alloc is not None:
                alloc.release(r.rid)
            clear_slot(b)

        def preempt_slot(b: int) -> None:
            """Recompute preemption: capture the victim's generated tokens
            into its request, release its page groups and re-queue it at
            the head — readmission re-prefills prompt+generated and
            continues at the same (rid, token-index) sampling keys."""
            nonlocal preemptions
            r = slot_req[b]
            r.generated = list(slot_out[b])
            r.preemptions += 1
            preemptions += 1
            alloc.release(r.rid)
            clear_slot(b)
            sched.resubmit(r)

        def admit_tokens(r: Request) -> int:
            """The admission reservation: worst-case prompt+max_new under
            ``reserve``, the actual prefill footprint under ``on_demand``
            (decode extends group-by-group from there).  Reads the LIVE
            policy — a retune swap changes what new admissions reserve."""
            if alloc is not None and sched.on_demand:
                return r.resident_tokens
            return r.total_tokens

        def shared_match(r: Request):
            """``(gids, covered, cow)`` the registry offers ``r``: live
            groups whose registered chunks cover a prefix of its
            prompt(+carried tokens), capped one token short of the full
            footprint so at least one suffix token always runs through
            prefill (its logits seed sampling).  ``cow`` is set when the
            suffix's first write lands *inside* the last shared group —
            that group must be split before admission completes.  Gated
            on the LIVE ``cfg.share_prefix`` (a retune knob): with
            sharing off the registry still registers (cheap, keeps it
            warm for a swap) but never matches."""
            if (prefix is None or not cfg.share_prefix
                    or r.frontend_embeds is not None):
                return [], 0, False
            toks = list(r.prompt) + list(r.generated)
            gids, covered = prefix.match(toks)
            covered = min(covered, len(toks) - 1)
            keep = -(-covered // self.group_tokens)
            return gids[:keep], covered, bool(covered % self.group_tokens)

        def try_admit(r: Request):
            """Secure ``r``'s page reservation: take refs on matched
            shared groups, extend with private groups for the rest, and
            CoW-split (allocator swap + device group copy) the boundary
            group the suffix will write into.  Returns ``(groups,
            covered)`` — the logical page-table row and the shared token
            count — or ``None`` when the pool cannot host ``r`` yet."""
            nonlocal cache, cow_splits
            gids, covered, cow = shared_match(r)
            if not gids:
                groups = alloc.try_alloc(r.rid, admit_tokens(r))
                return None if groups is None else (groups, 0)
            alloc.share(r.rid, gids)
            if alloc.extend(r.rid, admit_tokens(r)) is None:
                alloc.release(r.rid)  # undo: the shared refs must not leak
                return None
            if cow:
                old = gids[-1]
                new = alloc.cow_split(r.rid, len(gids) - 1)
                if new is None:
                    alloc.release(r.rid)
                    return None
                # the split group's resident tokens must read identically
                # through the new mapping: copy the physical bytes
                cache = dict(cache, blocks=self._copy_group(
                    cache["blocks"], jnp.asarray(old, jnp.int32),
                    jnp.asarray(new, jnp.int32)))
                cow_splits += 1
            return alloc.owned_groups(r.rid), covered

        def fits_shared(r: Request) -> bool:
            """Free-space test matching ``try_admit``'s arithmetic exactly
            (the sjf bypass scan must never disagree with admission):
            fresh groups needed = full reservation minus shared groups,
            plus one when a CoW split will claim a free group."""
            gids, covered, cow = shared_match(r)
            need = (alloc.groups_for(admit_tokens(r)) - len(gids)
                    + (1 if cow else 0))
            return need <= alloc.free_groups

        def next_admission():
            """(request, groups, covered) for the next admissible request,
            else None.  Head-first in policy order; under ``sjf`` a bounded
            bypass admits the first *fitting* pending request when the
            head's reservation doesn't fit (no head-of-line starvation);
            ``fifo``/``interleave`` stay strictly in order."""
            head = sched.peek()
            if alloc is None:
                return sched.pop(), None, 0
            got = try_admit(head)
            if got is not None:
                sched.pop()
                return head, got[0], got[1]
            if cfg.schedule != "sjf":
                return None
            cand = sched.pop_first_fit(fits_shared)
            if cand is None:
                return None
            got = try_admit(cand)
            # fits_shared IS try_admit's free-space arithmetic, so this
            # cannot be None — admitting with a stale page table would
            # corrupt KV
            assert got is not None, "pop_first_fit/try_admit disagree"
            return cand, got[0], got[1]

        def extend_slot(b: int, want: Optional[int] = None) -> None:
            """Grow slot ``b``'s reservation to cover the next decode
            write (``want`` tokens under speculation: every column of the
            verify chain that could be *accepted* must land in reserved
            groups, not scratch); on pool exhaustion preempt the
            cheapest-recompute victim — resident tokens minus the
            shared-prefix tokens other owners keep alive, ties youngest —
            and retry.  ``b`` itself may be the cheapest and get
            preempted — the caller re-filters ``active`` on ``slot_req``
            afterwards, which drops self-preempted slots from the
            dispatch."""
            r = slot_req[b]
            target = int(lengths[b]) + 1 if want is None else want
            while True:
                new = alloc.extend(r.rid, target)
                if new is not None:
                    if new:
                        grown = alloc.owned_groups(r.rid)
                        page_tables[b, :len(grown)] = grown
                    return
                occupied = [bb for bb in range(B)
                            if slot_req[bb] is not None]
                by_rid = {slot_req[bb].rid: bb for bb in occupied}

                def recompute_cost(rr: Request) -> int:
                    # tokens a preemption would force back through
                    # prefill: resident minus what shared groups keep
                    # alive for its readmission re-match
                    return max(0, int(lengths[by_rid[rr.rid]])
                               - alloc.shared_prefix_tokens(rr.rid))

                victim = SlotScheduler.select_victim(
                    [slot_req[bb] for bb in occupied],
                    cost=recompute_cost)
                vb = by_rid[victim.rid]
                preempt_slot(vb)
                if vb == b:
                    return

        def sample_key_for(b: int) -> None:
            nonlocal base_keys
            if cfg.temperature > 0:
                base_keys = base_keys.at[b].set(
                    self._base_key(slot_req[b].rid))

        def apply_knobs(knob_cfg: Dict[str, Any]) -> Dict[str, Any]:
            """Swap a retuned winner into the running loop at this step
            boundary — no drain, no recompile of live dispatch shapes
            (``max_batch`` caps ADMISSION; the physical slot count is
            compiled; a new ``draft_len`` only keys a different verify
            grid width, which jit caches per shape).  Tokens cannot
            change: sampling keys on (rid, token-index) only, and every
            knob here is token-parity-invariant by construction.
            Returns {knob: (old, new)} for the knobs that moved."""
            nonlocal slot_cap, ever_on_demand
            applied: Dict[str, Any] = {}
            new_cap = min(int(knob_cfg["max_batch"]), B)
            if new_cap != slot_cap:
                applied["max_batch"] = (slot_cap, new_cap)
                slot_cap = new_cap
            new_sched = str(knob_cfg["schedule"])
            if new_sched != cfg.schedule:
                applied["schedule"] = (cfg.schedule, new_sched)
                sched.set_policy(new_sched)  # re-sorts pending
                cfg.schedule = new_sched
            new_pp = str(knob_cfg.get("page_policy", cfg.page_policy))
            if alloc is not None and new_pp != cfg.page_policy:
                applied["page_policy"] = (cfg.page_policy, new_pp)
                sched.set_page_policy(new_pp)
                cfg.page_policy = new_pp
                if new_pp == "on_demand":
                    ever_on_demand = True
            new_chunk = int(knob_cfg["prefill_chunk"])
            if new_chunk != cfg.prefill_chunk:
                applied["prefill_chunk"] = (cfg.prefill_chunk, new_chunk)
                cfg.prefill_chunk = new_chunk
            new_draft = int(knob_cfg.get("draft_len", cfg.draft_len))
            if new_draft != cfg.draft_len:
                applied["draft_len"] = (cfg.draft_len, new_draft)
                cfg.draft_len = new_draft
            new_share = bool(int(knob_cfg.get(
                "share_prefix", int(cfg.share_prefix))))
            if alloc is not None and new_share != cfg.share_prefix:
                applied["share_prefix"] = (cfg.share_prefix, new_share)
                cfg.share_prefix = new_share
            return applied

        def admit(b: int, head: Request, groups, covered: int) -> None:
            """Seat ``head`` in free slot ``b`` on the reservation the
            pool granted: page table, shared-prefix credit, prefill
            chunks and sampling key."""
            nonlocal shared_total
            admit_t.setdefault(head.rid, time.time())
            if window is not None and head.rid not in seen_rids:
                seen_rids.add(head.rid)  # re-admissions don't
                window.record_request(steps, head.prompt, head.max_new)
            if groups is not None:
                page_tables[b, :] = PageAllocator.SCRATCH_GROUP
                page_tables[b, :len(groups)] = groups
            if covered:
                shared_total += covered
                shared_by_rid[head.rid] = (
                    shared_by_rid.get(head.rid, 0) + covered)
            slot_req[b] = head
            lengths[b] = covered
            chunk = cfg.prefill_chunk
            # a preempted request re-prefills its prompt plus the tokens
            # it had generated (exact chunked prefill ⇒ identical cache
            # state to the uninterrupted run); with prefix sharing the
            # covered leading tokens are already resident in shared
            # groups, so only the private suffix is prefilled at all —
            # the TTFT win
            toks = np.asarray(
                [(list(head.prompt) + list(head.generated))[covered:]],
                np.int32)
            slot_out[b] = list(head.generated)
            slot_chunks[b] = [toks[:, s:s + chunk]
                              for s in range(0, toks.shape[1], chunk)]
            slot_first_chunk[b] = True
            sample_key_for(b)

        def loop() -> None:
            nonlocal cache, decode_s, steps, drafted, accepted
            nonlocal admission_blocked
            while sched.has_pending or any(r is not None for r in slot_req):
                progressed = blocked = False
                # 1. admission into freed slots, in policy order; only
                # slots below slot_cap admit (the max_batch knob — slots
                # at/above a lowered cap simply drain and stay empty)
                for b in range(B):
                    if b >= slot_cap:
                        continue
                    if slot_req[b] is not None or not sched.has_pending:
                        continue
                    admitted = next_admission()
                    if admitted is None:
                        blocked = True
                        break  # pool full: wait for a release
                    head, groups, covered = admitted
                    with span(ADMIT, rid=head.rid, slot=b, covered=covered):
                        admit(b, head, groups, covered)
                    progressed = True
                    if not sched.interleave_prefill:
                        while slot_chunks[b] and slot_req[b] is not None:
                            run_chunk(b)
                # 2. pending prefill chunks: one per slot per step under
                # interleave, drained back-to-back otherwise (the drain
                # arm is only reachable after a retune swaps the policy
                # AWAY from interleave mid-prefill — admission drains
                # non-interleave slots inline above)
                for b in range(B):
                    if slot_req[b] is None or not slot_chunks[b]:
                        continue
                    if sched.interleave_prefill:
                        run_chunk(b)
                    else:
                        while slot_chunks[b] and slot_req[b] is not None:
                            run_chunk(b)
                    progressed = True
                # 3. one batched decode step over every decoding slot —
                # with speculation, draft_len extra n-gram columns ride
                # the same dispatch and the longest sample-matching draft
                # prefix is accepted; under on_demand, first grow
                # reservations to cover the step's KV writes (the whole
                # chain that could be accepted), preempting victims on
                # pool exhaustion
                active = [b for b in range(B)
                          if slot_req[b] is not None and not slot_chunks[b]]
                drafts: Dict[int, List[int]] = {}
                if cfg.draft_len > 0:
                    for b in active:
                        r = slot_req[b]
                        # never draft past the generation budget: tokens
                        # beyond max_new could not be accepted anyway
                        room = r.max_new - len(slot_out[b]) - 1
                        d = self._ngram_draft(
                            _tail_history(r.prompt, slot_out[b],
                                          cfg.draft_window),
                            min(cfg.draft_len, room))
                        if d:
                            drafts[b] = d
                if ever_on_demand:
                    for b in active:
                        if slot_req[b] is None:
                            continue  # preempted as a victim this pass
                        want = None
                        if b in drafts:
                            want = min(
                                int(lengths[b]) + 1 + len(drafts[b]),
                                slot_req[b].total_tokens)
                        extend_slot(b, want)
                    active = [b for b in active
                              if slot_req[b] is not None
                              and not slot_chunks[b]]
                step = steps
                if active and cfg.draft_len > 0:
                    t = time.time()
                    C = cfg.draft_len + 1
                    with span(DISPATCH, step=step, active=len(active)):
                        feed = np.zeros((B, C), np.int32)
                        feed[:, 0] = next_tok
                        for b, d in drafts.items():
                            if slot_req[b] is not None:
                                feed[b, 1:1 + len(d)] = d
                        logits, new_cache = self._decode_multi(
                            self.params, jnp.asarray(feed), cache,
                            jnp.asarray(lengths, jnp.int32),
                            jnp.asarray(page_tables) if self._paged
                            else None)
                        if cfg.temperature <= 0:
                            picked = self._argmax_grid(logits)
                        else:
                            produced = jnp.asarray(
                                [len(slot_out[b]) for b in range(B)],
                                jnp.int32)
                            picked = self._categorical_grid_j(
                                logits, base_keys, produced)
                    with span(READBACK, step=step):
                        toks = np.asarray(picked)
                    cache = new_cache
                    decode_s += time.time() - t
                    steps += 1
                    admission_blocked += blocked
                    progressed = True
                    with span(ACCEPT, step=step):
                        for b in active:
                            d = drafts.get(b, [])
                            drafted += len(d)
                            acc_b = 0
                            # column 0 is the ordinary sampled token
                            # (always accepted); column i+1's logits are
                            # valid only if fed draft token d[i] matched
                            # the token sampled at column i
                            for i in range(C):
                                lengths[b] += 1  # the fed token is resident
                                first_tok_t.setdefault(slot_req[b].rid,
                                                       time.time())
                                tok = int(toks[b, i])
                                accept_token(b, tok)
                                if i > 0:
                                    accepted += 1
                                    acc_b += 1
                                if slot_req[b] is None:
                                    break  # finished mid-chain
                                if i >= len(d) or tok != d[i]:
                                    break
                            if window is not None and d:
                                window.record_draft(len(d), acc_b)
                elif active:
                    t = time.time()
                    with span(DISPATCH, step=step, active=len(active)):
                        logits, new_cache = self._decode_multi(
                            self.params, jnp.asarray(next_tok[:, None]),
                            cache, jnp.asarray(lengths, jnp.int32),
                            jnp.asarray(page_tables) if self._paged
                            else None)
                        if cfg.temperature <= 0:
                            picked = self._argmax_multi(logits)
                        else:
                            produced = jnp.asarray(
                                [len(slot_out[b]) for b in range(B)],
                                jnp.int32)
                            picked = self._categorical_multi(
                                logits, base_keys, produced)
                    with span(READBACK, step=step):
                        toks = np.asarray(picked)
                    cache = new_cache
                    decode_s += time.time() - t
                    steps += 1
                    admission_blocked += blocked
                    progressed = True
                    with span(ACCEPT, step=step):
                        for b in active:
                            lengths[b] += 1  # the fed token is now resident
                            first_tok_t.setdefault(slot_req[b].rid,
                                                   time.time())
                            tok = int(toks[b])
                            if window is not None:
                                # shadow probe: what WOULD 1-token n-gram
                                # drafting have proposed, and would it
                                # have been accepted?  Feeds a measured
                                # acceptance rate even while draft_len=0,
                                # so the retuner can justify switching
                                # speculation ON — without it the loop
                                # could only ever turn it off.
                                pred = self._ngram_draft(
                                    _tail_history(slot_req[b].prompt,
                                                  slot_out[b],
                                                  cfg.draft_window), 1)
                                if pred:
                                    window.record_draft(
                                        1, 1 if pred[0] == tok else 0)
                            accept_token(b, tok)
                if window is not None:
                    window.record_depth(
                        sched.queue_depth
                        + sum(1 for r in slot_req if r is not None))
                    hit = retuner.maybe_retune(window, steps)
                    if hit is not None:
                        hit["applied"] = apply_knobs(hit["config"])
                        retunes.append(hit)
                if not progressed:  # defensive: cannot happen (paging.py)
                    raise RuntimeError(
                        "continuous scheduler stalled: pending requests "
                        "but no admissible slot, chunk or decode step")

        try:
            loop()
        except BaseException:
            # error-path unwind: no page group may outlive the generation
            # (a stranded reservation would silently shrink every later
            # run's pool); tests assert check_balanced() after this
            if alloc is not None:
                alloc.release_all()
            raise
        finally:
            # post-run pool introspection (tests/bench), even on unwind
            self.last_alloc = alloc
            self.last_prefix = prefix

        return GenerationResult(
            [list(t) for t in results], prefill_s, decode_s, steps,
            chunks_issued, [dict(r) for r in per_request],
            preemptions=preemptions, shared_prefix_tokens=shared_total,
            cow_splits=cow_splits, drafted=drafted, accepted=accepted,
            retunes=retunes, admission_blocked=admission_blocked)

    def _sample_slot(self, logits, rid: int, produced: int):
        """Sample ONE request's next token from (1, S, V) logits, keyed by
        the shared (request id, token index) scheme (``_base_key``)."""
        lg = logits[:, -1, :self.model.cfg.vocab_size].astype(jnp.float32)[0]
        if self.cfg.temperature <= 0:
            return jnp.argmax(lg).astype(jnp.int32)
        key = jax.random.fold_in(self._base_key(rid), produced)
        return jax.random.categorical(
            key, lg / self.cfg.temperature).astype(jnp.int32)
