"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the serve engine on this host (reduced configs by default) under
either runtime: the continuous-batching scheduler (default; mixed prompt
and generation lengths via ``--mixed``, tuned ``--schedule`` acting at
admission time, ``--kv-layout paged`` for the real page allocator) or the
legacy equal-length wave loop (``--runtime wave``).  ``--mesh DxM`` runs
the engine sharded over a (data, model) device grid — data-axis replicas
widen slot capacity, model-axis tensor parallelism splits heads/ff — on
CPU hosts the requested device count is faked via XLA host devices, so
the sharded paths exercise end-to-end without an accelerator.  This is
the interactive counterpart of the decode dry-run cells.
"""
from __future__ import annotations

import argparse
import os
import sys


def _mesh_argv(argv):
    """The ``--mesh`` value from a raw argv, pre-argparse.

    Needed before ``import jax``: on CPU hosts a multi-device mesh only
    exists if ``XLA_FLAGS`` fakes the host devices, and that flag is
    read once at backend init.
    """
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--mesh="):
            return a.split("=", 1)[1]
    return None


def _parse_mesh(s):
    try:
        data, model = (int(x) for x in s.lower().split("x"))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"mesh must be DATAxMODEL (e.g. 1x2, 2x4); got {s!r}")
    if data < 1 or model < 1:
        raise argparse.ArgumentTypeError(f"mesh axes must be >= 1: {s!r}")
    return (data, model)


_mesh = _mesh_argv(sys.argv)
if _mesh:
    try:
        _d, _m = (int(x) for x in _mesh.lower().split("x"))
    except ValueError:
        _d = _m = 1  # argparse reports the malformed value later
    if _d * _m > 1:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={_d * _m}")

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serve import ServeConfig, ServeEngine
from repro.serve.scheduler import PAGE_POLICIES, SCHEDULES, TP_MODES

__all__ = ["main"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--runtime", choices=("continuous", "wave"),
                    default="continuous")
    ap.add_argument("--kv-layout", choices=("dense", "paged"),
                    default="dense")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="KV pool size in 16-token pages (paged layout: "
                         "bounds how many requests stay resident)")
    ap.add_argument("--schedule", choices=SCHEDULES, default="fifo")
    ap.add_argument("--page-policy", choices=PAGE_POLICIES,
                    default="reserve",
                    help="paged-layout KV reservation policy: worst-case "
                         "up-front (reserve) or prompt-only + on-demand "
                         "growth with recompute preemption (on_demand)")
    ap.add_argument("--prefill-chunk", type=int, default=512)
    ap.add_argument("--mesh", type=_parse_mesh, default=None,
                    metavar="DATAxMODEL",
                    help="run sharded over a (data, model) device grid, "
                         "e.g. 2x1 (two replicated engines), 1x2 (one "
                         "2-way tensor-parallel engine), 2x4; CPU hosts "
                         "fake the devices via XLA_FLAGS automatically")
    ap.add_argument("--rules-preset", choices=("serve_tp", "serve_replicas"),
                    default="serve_tp",
                    help="logical-axis sharding rules for the mesh "
                         "(serve_tp also covers pure-replica meshes: its "
                         "size-1 model axis drops out)")
    ap.add_argument("--tp-vs-replicas", choices=TP_MODES, default="tp",
                    help="how a flat tuned device count would map onto "
                         "the mesh (recorded on the config; --mesh fixes "
                         "the grid explicitly)")
    ap.add_argument("--mixed", action="store_true",
                    help="mixed-length workload: prompt lengths in "
                         "[2, prompt-len], generation lengths in "
                         "[1, max-new] (continuous runtime only)")
    ap.add_argument("--retune", action="store_true",
                    help="online workload-aware retuning: fingerprint the "
                         "live request window, detect drift from the "
                         "deployed knobs' tuned signature and swap in a "
                         "warm-started retune mid-run (continuous "
                         "runtime; see repro.serve.workload)")
    ap.add_argument("--retune-threshold", type=float, default=0.25,
                    help="fingerprint distance that triggers a retune")
    ap.add_argument("--retune-budget", type=int, default=16,
                    help="surrogate tests per retune")
    ap.add_argument("--drift", action="store_true",
                    help="with --mixed: the second half of the requests "
                         "shifts to short-tail shared-prefix prompts, so "
                         "--retune has a drift to catch")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = reduced(get_config(args.arch))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    engine = ServeEngine(model, params, ServeConfig(
        max_seq=args.prompt_len + args.max_new + 8,
        batch_slots=args.batch_slots, temperature=args.temperature,
        seed=args.seed, runtime=args.runtime, kv_layout=args.kv_layout,
        kv_cache_pages=args.kv_pages, schedule=args.schedule,
        page_policy=args.page_policy, prefill_chunk=args.prefill_chunk,
        retune=args.retune, retune_threshold=args.retune_threshold,
        retune_budget=args.retune_budget, mesh_shape=args.mesh,
        rules_preset=args.rules_preset,
        tp_vs_replicas=args.tp_vs_replicas))
    rng = np.random.default_rng(args.seed)
    if args.mixed and engine._continuous:
        plens = rng.integers(2, args.prompt_len + 1, size=args.requests)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                   for n in plens]
        max_new = [int(m) for m in
                   rng.integers(1, args.max_new + 1, size=args.requests)]
        if args.drift:
            # second half: shared-prefix short-tail requests — a
            # workload shift the retuner's fingerprint can see
            half = args.requests // 2
            head = rng.integers(1, cfg.vocab_size,
                                size=max(2, args.prompt_len - 2)).tolist()
            for i in range(half, args.requests):
                prompts[i] = head + rng.integers(
                    1, cfg.vocab_size, size=2).tolist()
                max_new[i] = max(1, args.max_new // 4)
    else:
        prompts = rng.integers(1, cfg.vocab_size,
                               size=(args.requests,
                                     args.prompt_len)).tolist()
        max_new = args.max_new
    fe = None
    if cfg.frontend or cfg.encoder:
        fe = rng.normal(size=(args.requests, cfg.frontend_tokens,
                              cfg.frontend_dim)).astype(np.float32)
    res = engine.generate(prompts, max_new, frontend_embeds=fe)
    mode = f"{args.runtime}/{args.kv_layout}/{args.schedule}" \
        if engine._continuous else "wave"
    if engine.mesh is not None:
        d, m = engine.mesh_shape
        mode += f"/mesh{d}x{m}({args.rules_preset})"
    print(f"{cfg.name} [{mode}]: {args.requests} requests, "
          f"prefill {res.prefill_seconds:.2f}s, "
          f"decode {res.decode_seconds:.2f}s "
          f"({res.decode_tokens_per_sec:.1f} tok/s, {res.steps} steps, "
          f"p50 {res.p50_latency_s:.3f}s, p95 {res.p95_latency_s:.3f}s)")
    if getattr(engine, "last_alloc", None) is not None:
        a = engine.last_alloc
        print(f"  kv pool: {a.n_groups} groups x {a.group_tokens} tokens, "
              f"high water {a.high_water} groups "
              f"[{args.page_policy}, {res.preemptions} preemptions]")
    if args.retune:
        if not res.retunes:
            print("  retune: no workload shift detected")
        for ev in res.retunes:
            moved = ", ".join(f"{k} {old}->{new}"
                              for k, (old, new) in ev["applied"].items()) \
                or "no knob moved"
            print(f"  retune @step {ev['step']}: drift {ev['distance']:.2f}"
                  f" [{ev['warm_source']}] -> {moved} "
                  f"(accept {ev['measured_accept']:.2f})")
    for i, toks in enumerate(res.tokens[:3]):
        print(f"  req {i}: {toks[:16]}{'...' if len(toks) > 16 else ''}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
