import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# Must precede any jax import: the tuner is a CPU analysis that compiles
# against the 512-device production mesh, so it never takes an
# accelerator, even on a host that has one.
"""ACTS over the JAX runtime (the paper's technique applied to this system).

Four modes:

* ``--probe knob=v[,knob=v...]`` — one manual hypothesis test: compile the
  cell under the given knobs, print the roofline terms (the
  hypothesis→change→measure loop of EXPERIMENTS.md §Perf).
* ``--tune-kernels`` — ACTS over the *Pallas kernels* of the given cell:
  tune block configs for the cell's attention/rmsnorm shapes and persist
  them in the autotune cache, which later runs (``--kernel-autotune``,
  the serve engine, and bare ``repro.kernels.ops`` calls) consult.
* ``--joint`` — cross-system co-tuning: the serve engine's knobs AND the
  decode kernel's block config as ONE ``CompositeSUT`` under one budget
  (BestConfig-style subspace round-robin by default); ``--max-devices N``
  widens the serve subspace with sharding knobs (device count ×
  tp-vs-replicas layout) so the mesh is co-tuned too and the winner
  persists under its mesh-topology cache key.  The default scorer
  is the analytic co-deployment surrogate (``repro.serve.space``; the
  CI/benchmark path); ``--real`` instead wall-clocks the LIVE system per
  trial — the real ``ServeEngine`` rebuilt and timed under each candidate
  config, the real train step re-jitted and timed, train-step knobs
  joining the composite.  Winners persist to the autotune cache — kernel
  blocks under the tuned decode shape, serve knobs as a serve-config
  entry, and (``--real``) train knobs as a train-step entry.
* default — full ACTS run: LHS + RRS over the knob space within ``--budget``
  tests (each test = one AOT compile of the real system on the production
  mesh), reporting default vs. best and writing the full history.

Examples:
  python -m repro.launch.tune --arch qwen2.5-32b --shape train_4k --budget 24
  python -m repro.launch.tune --arch qwen2.5-32b --shape train_4k \
      --tune-kernels
  python -m repro.launch.tune --arch xlstm-350m --shape decode_32k \
      --joint --surrogate --budget 96
  python -m repro.launch.tune --arch grok-1-314b --shape train_4k \
      --probe expert_tp=true,rules_preset=dp
"""
import argparse
import json
import sys
import time
import traceback

from repro.configs import ARCH_IDS, SHAPES
from repro.launch.compile_cache import enable_compile_cache

__all__ = ["main"]


def _parse_value(v: str):
    if v.lower() in ("true", "false"):
        return v.lower() == "true"
    try:
        return int(v)
    except ValueError:
        return v


def _joint_main(args) -> int:
    """--joint: serve knobs + decode kernel blocks (+ train-step knobs in
    --real mode) co-tuned as one SUT under one budget."""
    from repro.configs import get_config
    from repro.core.tuner import Tuner

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    train_seq, train_batch = 32, 8  # the live train-step workload

    if args.real:
        from repro.configs import reduced
        from repro.serve.space import make_live_cotune_sut

        # Live wall-clock co-tuning: every trial rebuilds the REAL serve
        # engine and re-jits the REAL train step under the candidate knobs
        # and times them (warmup trimmed, median of repeats).  On this
        # host the model is the reduced same-family config so a budget-8
        # run finishes in CI time; pointing the same code path at the full
        # config on a TPU pod is a parameter change, not a port.
        model_cfg = reduced(cfg)
        max_seq = min(shape.seq_len, 128)
        sut = make_live_cotune_sut(model_cfg, max_seq=max_seq,
                                   train_seq=train_seq,
                                   train_batch=train_batch, seed=args.seed,
                                   repeats=args.real_repeats,
                                   max_devices=args.max_devices)
        mode = "joint-real"
        dtype = model_cfg.compute_dtype
        # Honest provenance: the live kernel member scored every candidate
        # at ONE fixed decode shape (the default batch), so the winner is
        # keyed at those dims — not at the tuned serve batch it was never
        # evaluated under.  (The surrogate path re-costs the kernel at the
        # candidate batch inside its scalarizer, so it keys at the tuned
        # batch; its dims are resolved after the run.)
        kernel_sig_dims = dict(sut.members["kernel"].dims)
        serve_sig_dims = {"S": max_seq, "H": model_cfg.padded_heads,
                          "KV": model_cfg.n_kv_heads,
                          "D": model_cfg.head_dim_}
    else:
        from repro.serve.space import CotuneParams, make_cotune_sut

        if not args.surrogate:
            print("[joint] scoring on the analytic co-deployment surrogate "
                  "(pass --real to wall-clock the live engine + train "
                  "step instead, or --surrogate to silence this note)")
        params = CotuneParams.from_model(cfg,
                                         max_seq=min(shape.seq_len, 32768))
        sut = make_cotune_sut(params, max_devices=args.max_devices)
        mode = "joint-surrogate"
        dtype = params.dtype
        kernel_sig_dims = None  # tuned-batch decode dims, known post-run
        serve_sig_dims = {"S": params.max_seq, "H": params.heads,
                          "KV": params.kv_heads, "D": params.head_dim}

    space = sut.space()
    tuner = Tuner(space, sut, budget=args.budget, optimizer=args.optimizer,
                  seed=args.seed, verbose=True)
    rep = tuner.run()

    parts = space.split(rep.best_config)
    serve_cfg, kernel_cfg = parts["serve"], parts["kernel"]
    train_cfg = parts.get("train")

    # Persist every winner in ONE cache file: kernel blocks under the
    # decode shape the tuned engine will actually run, serve knobs as the
    # serve-config entry, train-step knobs (live mode) as the train entry.
    from repro import autotune

    cache = autotune.default_cache()
    meta = {"mode": mode, "n_tests": rep.n_tests}
    if kernel_sig_dims is None:  # surrogate: key at the tuned serve batch
        kernel_sig_dims = params.decode_dims(serve_cfg["max_batch"])
    cache.put("decode_attention", autotune.shape_sig(kernel_sig_dims),
              dtype, autotune.backend_name(), kernel_cfg,
              rep.best_metric.value, meta=meta)
    # The serve winner keys at the mesh topology its own knobs chose:
    # a tuned 4-way TP layout must never be resolved by (or clobber)
    # the single-device entry the unsharded engine deploys from.
    n_dev = int(serve_cfg.get("mesh_devices", 1))
    if n_dev > 1 and str(serve_cfg.get("tp_vs_replicas")) == "replicas":
        winner_mesh = autotune.mesh_sig((n_dev, 1))
    elif n_dev > 1:
        winner_mesh = autotune.mesh_sig((1, n_dev))
    else:
        winner_mesh = autotune.mesh_sig(None)
    autotune.put_serve_config(serve_sig_dims, dtype, serve_cfg,
                              rep.best_metric.value, cache=cache, meta=meta,
                              mesh=winner_mesh)
    if train_cfg is not None:
        train_sig_dims = dict(serve_sig_dims, S=train_seq, B=train_batch)
        autotune.put_train_config(train_sig_dims, dtype, train_cfg,
                                  rep.best_metric.value, cache=cache,
                                  meta=meta)

    os.makedirs(args.out_dir, exist_ok=True)
    tag = f"joint_{args.arch}_{args.shape}" + \
        ("_real" if args.real else "")
    with open(os.path.join(args.out_dir, f"{tag}.json"), "w") as f:
        f.write(rep.to_json())

    d, b = rep.default_metric, rep.best_metric
    print("\n=== ACTS joint co-tuning result ===")
    print(f"cell: {args.arch} × {args.shape} "
          f"({'live wall-clock' if args.real else 'surrogate'}, "
          f"optimizer={args.optimizer})")
    print(f"default: {d.value:.1f} tok/s  (all-member defaults)")
    print(f"best:    {b.value:.1f} tok/s  "
          f"latency={b.metrics.get('latency_s', float('nan')):.3f}s")
    print(f"improvement: {rep.improvement:.2f}x in {rep.n_tests} tests "
          f"({rep.wall_seconds:.1f}s wall)")
    print(f"serve knobs:   {serve_cfg}")
    print(f"kernel blocks: {kernel_cfg}")
    if train_cfg is not None:
        print(f"train knobs:   {train_cfg}")
    print(f"persisted to {cache.path}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--budget", type=int, default=24)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--optimizer", default=None,
                    help="optimizer name (default: rrs; subspace_rr "
                         "for --joint)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--probe", default=None,
                    help="knob=v[,knob=v...]: single manual hypothesis test")
    ap.add_argument("--tune-kernels", action="store_true",
                    help="ACTS over the cell's Pallas kernel block configs; "
                         "winners persist in the autotune cache")
    ap.add_argument("--joint", action="store_true",
                    help="co-tune serve-engine knobs + decode kernel blocks "
                         "as one SUT (CompositeSpace, shared budget)")
    ap.add_argument("--surrogate", action="store_true",
                    help="with --joint: score on the analytic co-deployment "
                         "surrogate (the default/CI path; the flag just "
                         "silences the which-scorer note)")
    ap.add_argument("--real", action="store_true",
                    help="with --joint: wall-clock the LIVE system per "
                         "trial — rebuild the real ServeEngine and re-jit "
                         "the real train step under each candidate config "
                         "(reduced model on CPU hosts; warmup-trimmed "
                         "median timing); adds train-step knobs to the "
                         "composite and persists their winner too")
    ap.add_argument("--max-devices", type=int, default=1,
                    help="with --joint: widen the serve subspace with "
                         "sharding knobs (mesh_devices in powers of two "
                         "up to this count, tp_vs_replicas) so layout is "
                         "co-tuned with schedule and kernel blocks; the "
                         "winner persists under its mesh-topology cache "
                         "key; 1 = the historical unsharded space")
    ap.add_argument("--real-repeats", type=int, default=3,
                    help="with --joint --real: timed repeats per trial "
                         "(median taken); 1 = fastest smoke, 3 = default "
                         "noise rejection")
    ap.add_argument("--kernel-budget", type=int, default=16)
    ap.add_argument("--out-dir", default="results/tune")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.optimizer is None:
        args.optimizer = "subspace_rr" if args.joint else "rrs"
    if args.real and not args.joint:
        ap.error("--real only applies to --joint (live co-tuning)")
    if args.real and args.surrogate:
        ap.error("--surrogate and --real are mutually exclusive joint "
                 "scorers")

    if args.joint:
        return _joint_main(args)

    from repro.core.sut_jax import JaxDryRunSUT, knob_space
    from repro.core.tuner import Tuner

    kind = SHAPES[args.shape].kind

    if args.tune_kernels:
        from repro import autotune
        from repro.configs import get_config

        cfg = get_config(args.arch)
        shape = SHAPES[args.shape]
        attn_dims = {"B": 1, "S": shape.seq_len, "H": cfg.padded_heads,
                     "KV": cfg.n_kv_heads, "D": cfg.head_dim_}
        fa_dims = dict(attn_dims, SK=shape.seq_len)
        rn_dims = {"ROWS": shape.seq_len, "D": cfg.d_model}
        results = []
        # paged_attention shares the decode signature: its winner seeds the
        # continuous engine's pool layout (pages_per_block -> group size)
        for kernel, dims in (("flash_attention", fa_dims),
                             ("decode_attention", attn_dims),
                             ("paged_attention", attn_dims),
                             ("rmsnorm", rn_dims)):
            res = autotune.autotune_kernel(kernel, dims,
                                           dtype=cfg.compute_dtype,
                                           budget=args.kernel_budget,
                                           seed=args.seed)
            results.append(res)
            print(f"[autotune] {kernel} {res['sig']}: {res['config']} "
                  f"({res['mode']}, {res['n_tests']} tests, "
                  f"value {res['value']:.3g})")
        print(json.dumps({"cache": autotune.default_cache().path,
                          "entries": results}, indent=2))
        return 0
    sut = JaxDryRunSUT(args.arch, args.shape, multi_pod=args.multi_pod,
                       verbose=True)
    space = knob_space(kind)

    if args.probe is not None:
        config = space.default_config()
        if args.probe:
            for kv in args.probe.split(","):
                k, v = kv.split("=", 1)
                config[k] = _parse_value(v)
        space.validate(config)
        t0 = time.time()
        try:
            # the config the user asked for: a compile failure is the
            # answer, so it surfaces (the search path scores it inf)
            metric = sut.measure(config)
        except Exception:
            traceback.print_exc()
            print(f"probe failed: {args.arch} x {args.shape} under "
                  f"{config}", file=sys.stderr)
            return 1
        print(json.dumps({
            "arch": args.arch, "shape": args.shape,
            "config": {k: config[k] for k in sorted(config)},
            "value_s": metric.value,
            "metrics": metric.metrics,
            "wall_s": time.time() - t0,
        }, indent=2, default=str))
        return 0

    tuner = Tuner(space, sut, budget=args.budget,
                  optimizer=args.optimizer, seed=args.seed, verbose=True)
    rep = tuner.run()

    os.makedirs(args.out_dir, exist_ok=True)
    tag = f"{args.arch}_{args.shape}" + ("_mp" if args.multi_pod else "")
    with open(os.path.join(args.out_dir, f"{tag}.json"), "w") as f:
        f.write(rep.to_json())
    with open(os.path.join(args.out_dir, f"{tag}_records.jsonl"), "w") as f:
        for rec in sut.records:
            f.write(json.dumps(rec, default=str) + "\n")

    d, b = rep.default_metric, rep.best_metric
    print("\n=== ACTS result ===")
    print(f"cell: {args.arch} × {args.shape} "
          f"({'2x16x16' if args.multi_pod else '16x16'})")
    print(f"default: t_est={d.value:.4f}s dominant={d.metrics.get('dominant')}")
    print(f"best:    t_est={b.value:.4f}s dominant={b.metrics.get('dominant')}")
    print(f"speedup: {rep.improvement:.2f}x in {rep.n_tests} tests "
          f"({rep.wall_seconds:.0f}s wall)")
    print(f"best config: {rep.best_config}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
