"""The JAX distributed runtime as an ACTS system-under-tune.

This is the paper's architecture instantiated on this framework:

* **SystemManipulator** — applies a knob configuration by *re-jitting* the
  train/serve step under new sharding rules / remat / microbatching (the
  analogue of rewriting my.cnf and restarting mysqld; the restart cost is
  the XLA compile, which is exactly why the resource limit is counted in
  tests),
* **WorkloadGenerator** — the (architecture × input shape) cell; "running"
  the workload means AOT-compiling it for the production mesh and reading
  the roofline terms off the compiled artifact (the staging-environment
  measurement), or — for CPU-sized configs — actually timing real steps
  (``measured=True``),
* metric — estimated step seconds (max of the three roofline terms), to be
  minimized, with an HBM-capacity penalty so infeasible settings lose.

The knob space mirrors ``repro.train.step.RunKnobs``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

from repro.core.params import (
    BoolParam,
    Config,
    EnumParam,
    IntParam,
    ParameterSpace,
)
from repro.core.tuner import PerfMetric

__all__ = ["JaxDryRunSUT", "knob_space", "knobs_from_config",
           "JaxMeasuredSUT", "TrainStepSUT", "median_wall_clock"]

HBM_GIB = 16.0  # v5e


def knob_space(kind: str = "train", include_mesh_knobs: bool = True
               ) -> ParameterSpace:
    """The configuration-parameter space of the distributed runtime."""
    params = [
        EnumParam("rules_preset",
                  ("fsdp_tp", "tp", "dp", "dp_all", "fsdp_all"), "fsdp_tp"),
        EnumParam("remat", ("full", "dots", "none"), "full"),
        EnumParam("microbatches", (1, 2, 4, 8, 16), 4),
        EnumParam("loss_chunk", (0, 512, 2048), 512),
        EnumParam("moe_group", (1024, 4096, 16384), 4096),
        BoolParam("seq_shard", False),
        BoolParam("sp_residual", False),
        BoolParam("kv_seq_shard", False),
        BoolParam("expert_tp", False),
        BoolParam("pad_heads", False),
        BoolParam("head_dim_shard", False),
        EnumParam("attn_block_q", (0, 256, 512, 1024), 0),
        EnumParam("attn_block_kv", (0, 512, 1024, 2048), 0),
    ]
    if kind != "train":
        # decode/prefill: trainer-only knobs pinned by omission
        params = [p for p in params
                  if p.name not in ("remat", "microbatches", "loss_chunk")]
    return ParameterSpace(params)


def knobs_from_config(config: Config):
    from repro.train.step import RunKnobs

    fields = {f.name for f in dataclasses.fields(RunKnobs)}
    kwargs = {k: v for k, v in config.items() if k in fields}
    return RunKnobs(**kwargs)


class JaxDryRunSUT:
    """config -> compile the cell -> roofline-estimated step seconds."""

    def __init__(self, arch: str, shape: str, multi_pod: bool = False,
                 hbm_gib: float = HBM_GIB, verbose: bool = False):
        self.arch = arch
        self.shape = shape
        self.multi_pod = multi_pod
        self.hbm_gib = hbm_gib
        self.verbose = verbose
        self.name = f"jax[{arch}×{shape}]"
        self.records = []  # full dry-run records of every test

    def test(self, config: Config) -> PerfMetric:
        """Score a candidate the search generated: a config that fails to
        compile (or a skipped cell) loses with ``inf`` instead of
        crashing the tuner."""
        try:
            return self.measure(config)
        except Exception as e:
            if self.verbose:
                print(f"[sut_jax] compile failed for {config}: {e}")
            return PerfMetric(value=math.inf, higher_is_better=False,
                              metrics={"error": str(e)})

    def measure(self, config: Config) -> PerfMetric:
        """Compile the cell under ``config`` and score it; a compile error
        or a skipped cell raises.  The entry point for a config a user
        asked for (``repro.launch.tune --probe``)."""
        from repro.launch.dryrun import run_cell
        from repro.launch.roofline import roofline_terms

        knobs = knobs_from_config(config)
        rec = run_cell(self.arch, self.shape, multi_pod=self.multi_pod,
                       knobs=knobs, verbose=False)
        if rec.get("status") != "ok":
            raise RuntimeError(f"cell {self.arch} x {self.shape} not run: "
                               f"{rec.get('reason', 'skipped')}")
        terms = roofline_terms(rec)
        t = terms["t_est_s"]
        # HBM feasibility penalty on the resident estimate (exact argument
        # bytes + modeled activations; the CPU backend's temp_size is kept
        # as a diagnostic only): +1x per HBM of overflow steers the search
        # back into feasible territory instead of a cliff.
        mem = terms.get("resident_gib")
        penalty = 1.0
        if mem is not None and mem > self.hbm_gib:
            penalty += (mem - self.hbm_gib) / self.hbm_gib
        value = t * penalty
        rec["tuner_config"] = dict(config)
        rec["tuner_value"] = value
        self.records.append(rec)
        if self.verbose:
            print(f"[sut_jax] t_est={t:.4f}s penalty={penalty:.2f} "
                  f"dom={terms['dominant']} cfg={config}")
        return PerfMetric(
            value=value, higher_is_better=False,
            metrics={
                "t_est_s": t,
                "compute_s": terms["compute_s"],
                "memory_s": terms["memory_s"],
                "collective_s": terms["collective_s"],
                "dominant": terms["dominant"],
                "roofline_fraction": terms["roofline_fraction"],
                "resident_gib": mem,
                "mem_gib_per_device": terms.get("mem_gib_per_device"),
                "penalty": penalty,
            })


def _measured_train_setup(cfg, knobs, seq_len: int, global_batch: int,
                          n_batches: int, seed: int, donate: bool = False):
    """Shared scaffolding for wall-clock train-step SUTs: build the model,
    init state, jit the step under the knobs, and materialize the batch
    list (synthetic frontend embeddings included for frontend/encoder
    models).  Returns (step_fn, params, opt_state, batches)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import DataConfig, SyntheticLMDataset
    from repro.models import Model
    from repro.optim import OptimizerConfig
    from repro.train.step import init_train_state, make_train_step

    model = Model(cfg)
    params, opt_state = init_train_state(
        model, jax.random.PRNGKey(seed), knobs)
    data = SyntheticLMDataset(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq_len,
        global_batch=global_batch, seed=seed))
    step_fn = jax.jit(make_train_step(model, OptimizerConfig(), knobs),
                      donate_argnums=(0, 1) if donate else ())
    batches = [
        {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        for i in range(n_batches)
    ]
    if cfg.frontend or cfg.encoder:
        rng = np.random.default_rng(seed)
        for b in batches:
            b["frontend_embeds"] = jnp.asarray(rng.normal(
                size=(global_batch, cfg.frontend_tokens,
                      cfg.frontend_dim)).astype(np.float32))
    return step_fn, params, opt_state, batches


def median_wall_clock(fn, warmup: int = 1, repeats: int = 3) -> float:
    """Median wall-clock seconds of ``fn()`` after trimming warmup runs.

    The shared timing methodology of the live (``--real``) co-tuning path:
    ``warmup`` untimed calls absorb compilation and cache effects, then the
    median of ``repeats`` timed calls rejects scheduler-noise outliers that
    a mean (or a single run) would leak into the tuner's objective.
    ``fn`` must block until its work is done (e.g. ``block_until_ready``).
    """
    import time

    for _ in range(max(0, warmup)):
        fn()
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


class TrainStepSUT:
    """The REAL train step as a system-under-tune (live co-tuning member).

    Each test applies the candidate knobs (``repro.train.space``) by
    re-jitting ``make_train_step`` — the paper's apply-config-and-restart —
    and wall-clocks a short microbatch training loop: ``warmup`` untimed
    loops (compile included), then the median of ``repeats`` timed loops of
    ``steps`` steps each.  The metric is training tokens/sec (higher is
    better); step seconds and the final loss ride along as provenance.
    """

    def __init__(self, cfg, seq_len: int = 32, global_batch: int = 8,
                 steps: int = 2, warmup: int = 1, repeats: int = 3,
                 seed: int = 0, rules_preset: str = "dp"):
        self.cfg = cfg
        self.seq_len = seq_len
        self.global_batch = global_batch
        self.steps = steps
        self.warmup = warmup
        self.repeats = repeats
        self.seed = seed
        self.rules_preset = rules_preset
        self.name = f"train-step[{cfg.name}]"

    def space(self) -> ParameterSpace:
        from repro.train.space import train_knob_space

        return train_knob_space(max_microbatches=self.global_batch)

    def test(self, config: Config) -> PerfMetric:
        import jax

        from repro.train.space import apply_train_knobs
        from repro.train.step import RunKnobs

        knobs = apply_train_knobs(
            config, RunKnobs(rules_preset=self.rules_preset))
        step_fn, params, opt_state, batches = _measured_train_setup(
            self.cfg, knobs, self.seq_len, self.global_batch, self.steps,
            self.seed)
        state = {"params": params, "opt": opt_state, "m": None}

        def loop():
            p, o = state["params"], state["opt"]
            for b in batches:
                p, o, m = step_fn(p, o, b)
            jax.block_until_ready(m["loss"])
            state.update(params=p, opt=o, m=m)

        sec = median_wall_clock(loop, self.warmup, self.repeats) / self.steps
        tput = self.seq_len * self.global_batch / sec
        return PerfMetric(
            value=tput, higher_is_better=True,
            metrics={"step_seconds": sec, "tokens_per_sec": tput,
                     "loss": float(state["m"]["loss"]),
                     "warmup": self.warmup, "repeats": self.repeats})


class JaxMeasuredSUT:
    """Real measured tuning for CPU-scale configs: config -> steps/sec.

    This exercises the paper's actual loop (apply config, restart system,
    run workload, measure) end-to-end on hardware we do have.
    """

    def __init__(self, cfg, seq_len: int = 128, global_batch: int = 8,
                 steps: int = 6, warmup: int = 2, seed: int = 0):
        self.cfg = cfg
        self.seq_len = seq_len
        self.global_batch = global_batch
        self.steps = steps
        self.warmup = warmup
        self.seed = seed
        self.name = f"jax-measured[{cfg.name}]"

    def space(self) -> ParameterSpace:
        return ParameterSpace([
            EnumParam("remat", ("full", "dots", "none"), "full"),
            EnumParam("microbatches", (1, 2, 4), 1),
            EnumParam("loss_chunk", (0, 32, 64), 0),
            BoolParam("donate", True),
            EnumParam("scan_unroll", (1, 2), 1),
        ])

    def test(self, config: Config) -> PerfMetric:
        import time

        import jax

        from repro.train.step import RunKnobs

        knobs = RunKnobs(
            remat=config["remat"], microbatches=config["microbatches"],
            loss_chunk=config["loss_chunk"], donate=config["donate"],
            scan_unroll=config["scan_unroll"], rules_preset="dp")
        step_fn, params, opt_state, batches = _measured_train_setup(
            self.cfg, knobs, self.seq_len, self.global_batch,
            self.warmup + self.steps, self.seed, donate=knobs.donate)
        for i in range(self.warmup):  # includes compile
            params, opt_state, m = step_fn(params, opt_state, batches[i])
        jax.block_until_ready(m["loss"])
        t0 = time.time()
        for i in range(self.warmup, self.warmup + self.steps):
            params, opt_state, m = step_fn(params, opt_state, batches[i])
        jax.block_until_ready(m["loss"])
        dt = (time.time() - t0) / self.steps
        tput = self.seq_len * self.global_batch / dt
        return PerfMetric(value=tput, higher_is_better=True,
                          metrics={"step_seconds": dt,
                                   "tokens_per_sec": tput,
                                   "loss": float(m["loss"])})
