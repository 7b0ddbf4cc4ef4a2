#!/usr/bin/env python3
"""Chip smoke run: the paged continuous serve path and its kernel tuner.

Serves Qwen2.5-32B at its published widths through the normal objects
(``get_config``, ``Model``, ``ServeEngine(...).generate``) on a TPU, checks
the output against the model's own reference forward pass and the paged
kernel against its jnp oracle, and runs the ACTS kernel tuner in
wall-clock mode on the chip.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # a 2x2 v5e host: TP (1,4) and
                                       # replicas (4,1) vs one chip

Weights are random, drawn from ``--seed``.  The script exits non-zero
when JAX finds no TPU, when the repository's ``src/`` is missing, and
when any phase fails.  The last line of standard output is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Tuner caches and a summary land in ``chiprun_out/smoke/``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "smoke")

# Greedy serving mix: 16 requests, 8 slots, 32 new tokens each.  Prompt
# lengths are multiples of 256 in [256, 1024]: with prefill_chunk 512
# every prompt splits into chunks of 512 and 256 tokens only, and each
# distinct chunk length is its own compiled program.
N_REQUESTS = 16
PROMPT_LENS = (256, 512, 768, 1024)
MAX_NEW = 32
SLOTS = 8
PREFILL_CHUNK = 512
PAGES_PER_GROUP = 4  # 64-token page groups: the paged kernel's tile
TUNE_BUDGET = 3  # trials per kernel the tuned engine tunes

# --four-chips: a lighter mix (three engines compile and serve in turn)
FOUR_CHIP_REQUESTS = 8
FOUR_CHIP_PROMPT_LENS = (256, 512)
FOUR_CHIP_MAX_NEW = 16
FOUR_CHIP_SLOTS = 2  # per replica

# Tolerances (bfloat16 weights and activations; 2**-7 is one bfloat16 ulp
# relative to the value, 8 significand bits).
# * Kernel vs ``paged_attention_ref``: both accumulate in float32 from the
#   same bfloat16 inputs and round the result to bfloat16 once, so they
#   agree to one bfloat16 ulp at the output's scale.
KERNEL_TOL = 2.0 ** -7
# * Served logits vs ``Model.forward``, as the relative L2 gap over the
#   vocabulary: the same weights through two programs (chunked paged
#   prefill + two kernel decode steps against one full-sequence pass)
#   round bfloat16 activations at different points, and random weights
#   carry that rounding through all 8 layers.  A CPU rehearsal of these
#   paths at 8 layers measured gaps of 0.053, 0.017 and 0.014 at d_model
#   512, 1024 and 2048 (shrinking with width), against 2.5e-5 for the
#   same paths in float32, so the gap is rounding, not logic; a decode
#   fed the wrong lengths measured 1.13.  On a v5e at d_model 5120 the
#   gap is 0.037 (XLA:TPU rounds at other points than XLA:CPU).  2**-4
#   sits above the rounding and far below any logic error.
LOGITS_TOL = 2.0 ** -4


def smoke_config():
    """Qwen2.5-32B at published widths, depth cut from 64 to 8 layers.

    Widths are the published ones: d_model 5120, 40 query / 8 KV heads of
    128, SwiGLU d_ff 27648, untied 152064-token vocabulary, QKV bias,
    bfloat16.  The cut keeps 8 of the 64 (identical) ``attn`` layers:
    about 5.46 B parameters, 10.9 GB in bfloat16, so one 16 GB v5e holds
    them beside the KV pool.  It stands for one stage of an 8-stage
    pipeline-parallel Qwen2.5-32B deployment: every layer's matmuls,
    attention kernel and KV bytes per token are the full model's.
    """
    from repro.configs import get_config

    cfg = get_config("qwen2.5-32b")
    return dataclasses.replace(cfg, name="qwen2.5-32b-8of64", n_layers=8)


def four_chip_config():
    """The ``--four-chips`` model: the same widths in float32, 2 of 64
    layers.

    Token identity across layouts holds in float32 only.  Tensor
    parallelism sums each row-parallel matmul (attention output, MLP down
    projection) as per-chip partial sums plus an all-reduce, in another
    order than one chip.  In bfloat16 that rounds differently, and greedy
    decoding over random weights flips near-tied argmaxes: in a CPU
    rehearsal at d_model 512, 2 of 8 requests diverged under TP (1,4) in
    bfloat16 and none in float32; replicas matched in both.  With float32
    weights and float32-precise matmuls the reordering stays far below any
    token margin.  At 4 bytes a weight, 2 layers (2.53 B parameters,
    10.1 GB) is the depth at which the unsharded reference still fits one
    chip.
    """
    return dataclasses.replace(smoke_config(), name="qwen2.5-32b-2of64-f32",
                               n_layers=2, param_dtype="float32",
                               compute_dtype="float32")


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------
class CompileClock:
    """Sums JAX's own compile-phase durations (trace, lowering, backend
    compile or persistent-cache load), counts backend compiles and the
    persistent-cache hits among them, so compile seconds are reported
    apart from run seconds."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            if event == self.EVENTS[-1]:
                self.compiles += 1

    def _on_count(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def mark(self):
        return self.seconds, self.compiles

    def since(self, mark):
        return self.seconds - mark[0], self.compiles - mark[1]


def emit(key, value):
    print(f"{key}: {value}", flush=True)


def workload(seed, n, lens, max_new, vocab):
    import numpy as np

    rng = np.random.default_rng(seed)
    plens = rng.choice(lens, size=n)
    prompts = [rng.integers(1, vocab, size=int(p)).tolist() for p in plens]
    return prompts, [max_new] * n


def serve_config(max_seq, slots, **kw):
    from repro.serve import ServeConfig

    return ServeConfig(max_seq=max_seq, batch_slots=slots,
                       prefill_chunk=PREFILL_CHUNK, runtime="continuous",
                       kv_layout="paged", kv_page_block=PAGES_PER_GROUP,
                       temperature=0.0, **kw)


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def serve(model, params, scfg, prompts, max_new, clock, tag):
    """Warm every program shape, then serve the mix with no compile in
    the timed window.  Returns (engine, result)."""
    from repro.serve import ServeEngine

    engine = ServeEngine(model, params, scfg)
    # 768-token prompts (chunks of 512 and 256), one more than the slots,
    # 3 tokens each: prefill into a fresh pool, prefill and decode after a
    # decode — under a mesh each of those sees its own input sharding
    warm = [prompts[0][:1] * 768] * (engine.cfg.batch_slots + 1)
    mark = clock.mark()
    t0 = time.perf_counter()
    engine.generate(warm, 3)
    warm_s = time.perf_counter() - t0
    compile_s, n_compiles = clock.since(mark)
    emit(f"{tag}.compile_seconds", compile_s)
    emit(f"{tag}.warmup_seconds", warm_s)
    emit(f"{tag}.warmup_compiles", n_compiles)
    mark = clock.mark()
    t0 = time.perf_counter()
    res = engine.generate(prompts, max_new)
    run_s = time.perf_counter() - t0
    _, late = clock.since(mark)
    emit(f"{tag}.run_seconds", run_s)
    emit(f"{tag}.compiles_in_run", late)
    emit(f"{tag}.requests", len(prompts))
    emit(f"{tag}.decode_steps", res.steps)
    emit(f"{tag}.prefill_chunks", res.prefill_chunks)
    emit(f"{tag}.tokens", sum(len(t) for t in res.tokens))
    assert all(len(t) == m for t, m in zip(res.tokens, max_new)), \
        f"{tag}: a request ended short of its max_new_tokens"
    assert late == 0, f"{tag}: {late} compiles inside the timed run"
    return engine, res


def assert_kernel_in_decode_step(engine):
    """The jitted decode step must contain the Pallas paged kernel: no
    interpret mode, no jnp reference on the served path."""
    import jax
    import jax.numpy as jnp

    B = engine.cfg.batch_slots
    i32 = jnp.int32
    pool = jax.eval_shape(lambda: engine.model.init_paged_cache(
        engine.pool_groups, engine.group_tokens))
    text = engine._decode_multi.lower(
        engine.params, jax.ShapeDtypeStruct((B, 1), i32), pool,
        jax.ShapeDtypeStruct((B,), i32),
        jax.ShapeDtypeStruct((B, engine.max_groups), i32),
    ).compile().as_text()
    found = text.count("tpu_custom_call")
    emit("decode_step.tpu_custom_call", found)
    assert found > 0, "decode step has no Pallas kernel (fallback taken)"


def logits_vs_forward(engine, prompt):
    """Paged prefill + two decode steps through the kernel, then the last
    position's logits against ``Model.forward`` on the same tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model, params = engine.model, engine.params
    V = model.cfg.vocab_size
    B, maxg = engine.cfg.batch_slots, engine.max_groups
    cache = engine._init_continuous_cache()
    tables = np.zeros((B, maxg), np.int32)  # idle rows: scratch group 0
    tables[0] = np.arange(1, maxg + 1)
    logits, cache = engine._slot_chunk_paged(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, cache,
        jnp.asarray(tables[0]), jnp.asarray(0, jnp.int32),
        jnp.asarray(0, jnp.int32))
    toks = list(prompt)
    for _ in range(2):
        toks.append(int(jnp.argmax(logits[0, -1, :V])))
        # fresh host arrays per step: jnp.asarray may alias a NumPy
        # buffer, and the dispatch is asynchronous
        feed = np.zeros((B, 1), np.int32)
        feed[0, 0] = toks[-1]
        lengths = np.zeros(B, np.int32)
        lengths[0] = len(toks) - 1  # resident before the fed token
        logits, cache = engine._decode_multi(
            params, jnp.asarray(feed), cache, jnp.asarray(lengths),
            jnp.asarray(tables))
    served = np.asarray(logits[0, -1, :V], np.float32)

    def last_logits(p, t):
        hidden, _ = model.forward(p, {"tokens": t})
        return model._logits(p, hidden[:, -1:])

    ref = np.asarray(jax.jit(last_logits)(
        params, jnp.asarray([toks], jnp.int32))[0, -1, :V], np.float32)
    rel = float(np.linalg.norm(served - ref) / np.linalg.norm(ref))
    emit("logits.max_abs_gap", float(np.abs(served - ref).max()))
    emit("logits.ref_scale", float(np.abs(ref).max()))
    emit("logits.rel_l2_gap", rel)
    emit("logits.tolerance", LOGITS_TOL)
    emit("logits.argmax_agree", int(served.argmax() == ref.argmax()))
    assert np.isfinite(served).all(), "served logits not finite"
    assert rel <= LOGITS_TOL, \
        f"served logits off the forward pass: rel L2 {rel} > {LOGITS_TOL}"


def kernel_vs_ref(engine, seed):
    """The paged kernel against ``paged_attention_ref`` at the smoke's
    own pool shapes, random pool contents and a random page table."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import paged_flash_decode
    from repro.kernels.paged_attention import paged_attention_ref

    mcfg = engine.model.cfg
    B, maxg = engine.cfg.batch_slots, engine.max_groups
    G, T = engine.pool_groups, engine.group_tokens
    KV, H, D = mcfg.n_kv_heads, mcfg.padded_heads, mcfg.head_dim_
    rng = np.random.default_rng(seed)
    bf16 = jnp.bfloat16
    q = jnp.asarray(rng.normal(size=(B, H, D)), bf16)
    kp = jnp.asarray(rng.normal(size=(G, KV, T, D)), bf16)
    vp = jnp.asarray(rng.normal(size=(G, KV, T, D)), bf16)
    pt = jnp.asarray((1 + rng.permutation(G - 1)[:B * maxg]).reshape(
        B, maxg), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, maxg * T + 1, size=B), jnp.int32)
    out = np.asarray(jax.jit(paged_flash_decode)(q, kp, vp, pt, lengths),
                     np.float32)
    ref = np.asarray(jax.jit(paged_attention_ref)(q, kp, vp, pt, lengths),
                     np.float32)
    scale = float(np.abs(ref).max())
    gap = float(np.abs(out - ref).max())
    emit("kernel.pool_shape", tuple(kp.shape))
    emit("kernel.max_abs_gap", gap)
    emit("kernel.ref_scale", scale)
    emit("kernel.tolerance", KERNEL_TOL)
    assert gap <= KERNEL_TOL * scale, \
        f"paged kernel off its reference: {gap} > {KERNEL_TOL} * {scale}"


def tune(model, params, scfg):
    """One engine with ``autotune_kernels``: ACTS times each kernel the
    engine tunes on the chip (``KernelSUT`` in ``mode="time"``)."""
    from repro import autotune
    from repro.autotune.space import shape_sig
    from repro.serve import ServeEngine

    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, "autotune.json")
    for stale in (path, path + ".lock"):
        if os.path.exists(stale):
            os.remove(stale)
    os.environ["REPRO_AUTOTUNE_CACHE"] = path
    autotune.reset_default_cache()
    t0 = time.perf_counter()
    engine = ServeEngine(model, params, dataclasses.replace(
        scfg, autotune_kernels=True, autotune_budget=TUNE_BUDGET))
    emit("tuner.seconds", time.perf_counter() - t0)
    mcfg = model.cfg
    dims = {"B": scfg.batch_slots, "S": scfg.max_seq,
            "H": mcfg.padded_heads, "KV": mcfg.n_kv_heads,
            "D": mcfg.head_dim_}
    cache = autotune.default_cache()
    tuned = sorted(k for k in engine.kernel_blocks)
    assert tuned, "the tuned engine tuned no kernel"
    for kernel in tuned:
        entry = cache.get(kernel, shape_sig(dims), mcfg.compute_dtype,
                          autotune.backend_name())
        assert entry is not None, f"no cache entry for {kernel}"
        meta = entry.get("meta", {})
        emit(f"tuner.{kernel}",
             json.dumps({"config": entry["config"], "seconds": entry["value"],
                         "default_seconds": meta.get("default_value"),
                         "mode": meta.get("mode"),
                         "trials": meta.get("n_tests")}, sort_keys=True))
        assert meta.get("mode") == "time", f"{kernel} not timed on the chip"
        assert 0 < entry["value"] < float("inf"), f"{kernel}: no timing"
    emit("tuner.cache", os.path.relpath(path, ROOT))


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def one_chip(args, clock):
    import jax

    model, params = build(smoke_config(), args.seed, clock)
    prompts, max_new = workload(args.seed, N_REQUESTS, PROMPT_LENS, MAX_NEW,
                                model.cfg.vocab_size)
    scfg = serve_config(max(PROMPT_LENS) + MAX_NEW, SLOTS)
    engine, res = serve(model, params, scfg, prompts, max_new, clock, "serve")
    emit("serve.peak_bytes_in_use", peak_bytes(jax.devices()[0]))
    assert_kernel_in_decode_step(engine)
    logits_vs_forward(engine, prompts[0][:PROMPT_LENS[0]])
    kernel_vs_ref(engine, args.seed)
    del engine
    tune(model, params, scfg)
    emit("peak_bytes_in_use", peak_bytes(jax.devices()[0]))
    return {"tokens": res.tokens, "steps": res.steps}


def four_chips(args, clock):
    """TP (1,4) and replicas (4,1) against the unsharded engine on one of
    the same chips: identical tokens; TP issues exactly the unsharded
    decode-dispatch count, replicas fewer (4x the slots).  Each layout
    re-shards the previous one's weights, so all three serve the same
    bits and at most two copies are ever resident."""
    import jax

    n = len(jax.devices())
    assert n == 4, f"--four-chips needs 4 devices, JAX found {n}"
    model, params = build(four_chip_config(), args.seed, clock)
    prompts, max_new = workload(args.seed, FOUR_CHIP_REQUESTS,
                                FOUR_CHIP_PROMPT_LENS, FOUR_CHIP_MAX_NEW,
                                model.cfg.vocab_size)
    max_seq = max(PROMPT_LENS) + MAX_NEW
    runs = {}
    for tag, mesh in (("one_chip", None), ("tp_1x4", (1, 4)),
                      ("replicas_4x1", (4, 1))):
        # float32-precise matmuls (the TPU default for float32 rounds
        # matmul inputs to bfloat16): see four_chip_config
        with jax.default_matmul_precision("highest"):
            engine, res = serve(model, params, serve_config(
                max_seq, FOUR_CHIP_SLOTS, mesh_shape=mesh), prompts,
                max_new, clock, tag)
        params = engine.params  # the next layout re-shards these
        runs[tag] = res
        del engine
        emit(f"{tag}.peak_bytes_in_use",
             max(peak_bytes(d) or 0 for d in jax.devices()))
    base = runs["one_chip"]
    for tag in ("tp_1x4", "replicas_4x1"):
        same = runs[tag].tokens == base.tokens
        emit(f"{tag}.tokens_match_one_chip", int(same))
        assert same, f"{tag} tokens differ from the one-chip engine"
    assert runs["tp_1x4"].steps == base.steps, "TP changed the step count"
    assert runs["replicas_4x1"].steps < base.steps, \
        "replicas did not cut decode steps"
    return {"tokens": base.tokens,
            "steps": {k: v.steps for k, v in runs.items()}}


def build(cfg, seed, clock):
    import jax

    from repro.models import Model

    model = Model(cfg)
    mark = clock.mark()
    t0 = time.perf_counter()
    params = model.init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    emit("model", cfg.name)
    emit("model.params", sum(x.size for x in jax.tree_util.tree_leaves(params)))
    emit("model.init_seconds", time.perf_counter() - t0)
    emit("model.init_compile_seconds", clock.since(mark)[0])
    return model, params


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip TP / replicas comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    emit("compile_cache", enable_compile_cache())
    emit("devices", f"{len(devices)} x {dev.device_kind} ({dev.platform})")
    clock = CompileClock()
    t0 = time.perf_counter()
    summary = (four_chips if args.four_chips else one_chip)(args, clock)
    emit("total_seconds", time.perf_counter() - t0)
    emit("compile_seconds_total", clock.seconds)
    emit("compiles_total", clock.compiles)
    emit("compile_cache_hits", clock.cache_hits)
    os.makedirs(OUT_DIR, exist_ok=True)
    name = "four_chips.json" if args.four_chips else "one_chip.json"
    with open(os.path.join(OUT_DIR, name), "w") as f:
        json.dump(summary, f)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
