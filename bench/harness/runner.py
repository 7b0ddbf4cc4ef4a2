"""One run of one cell: set-up, the measured window, the check.

``run_cell`` does everything but the look for a chip, so that a test can
drive it on the CPU at a small size.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import spec, stats
from .clock import CompileClock
from .program import Program

WINDOW_SPAN = "bench.window"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def enable_compile_cache(root: str = spec.ROOT) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set,
    else ``<checkout>/.jax_cache``.  Every program is written to it, so
    only a checkout's first run of a cell compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclass
class Run:
    """What a per-layer metric's reader may read."""
    cell: spec.Cell
    dims: Any  # the family's dims(cfg)
    chips: int
    peaks: Dict[str, float]
    results: List[Any]  # GenerationResult of each call in the window
    trace: Any = None  # bench.harness.trace.Trace of the traced window
    family: Any = None  # the family's module: its counts
    model_shards: int = 1  # chips that share each layer: the mesh's model axis

    @property
    def served(self) -> List[Tuple[int, int]]:
        """(prompt_len, new_tokens) of every request in the window."""
        return [(r["prompt_len"], r["new_tokens"])
                for r in stats.requests(self.results)]


def _window(prog: Program, mix, seconds: float):
    calls, results = [], []
    start = time.perf_counter()
    k = 0
    while True:
        prompts, max_new = mix.call(k)
        results.append(prog.generate(prompts, max_new))
        calls.append((prompts, max_new))
        k += 1
        if time.perf_counter() - start >= seconds:
            return calls, results, time.perf_counter() - start


def _traced_window(prog: Program, mix, log_dir: Optional[str] = None):
    """One whole call of the mix under the profiler.  The Python tracer
    stays off: it would slow the engine's host loop and so inflate the
    idle share it is read for; the runtime's own host spans (dispatch,
    transfers, waits on results) are kept.  The profile is written to a
    temporary directory and deleted, or kept in ``log_dir``."""
    import jax

    from . import trace as trace_mod

    tdir = log_dir or tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        prompts, max_new = mix.call(0)
        jax.profiler.start_trace(tdir, profiler_options=opts)
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            res = prog.generate(prompts, max_new)
        window_s = time.perf_counter() - start
        jax.profiler.stop_trace()
        tr = trace_mod.Trace.from_dir(tdir, WINDOW_SPAN)
    finally:
        if log_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
    return [(prompts, max_new)], [res], window_s, tr


def check_sample(calls, results, n: int, seed: int) -> List[Tuple]:
    """``n`` finished requests drawn from the seed, the longest among them:
    (prompt, served tokens)."""
    served = [(p, toks) for (prompts, _), res in zip(calls, results)
              for p, toks in zip(prompts, res.tokens)]
    longest = max(range(len(served)),
                  key=lambda i: len(served[i][0]) + len(served[i][1]))
    rng = np.random.default_rng([int(seed) % 2 ** 64, 1])
    rest = [i for i in rng.permutation(len(served)) if i != longest]
    return [served[i] for i in [longest] + rest[:max(0, n - 1)]]


def served_gaps(weights, cfg: Dict[str, Any], sample,
                precision: str = "float32",
                bench_dir: str = spec.BENCH_DIR) -> Dict[str, Any]:
    """The family's reference over each sampled prompt with its served
    tokens: how far each served token's logit lies below the reference's
    best.  With ``precision="fp8"`` the served tokens are replaced by what
    the fp8 reference puts first (the control)."""
    import jax

    ref = spec.reference(cfg, bench_dir)
    rspec = ref.Spec.from_config(cfg)
    widest, n_tok, agree = 0.0, 0, 0
    for prompt, out in sample:
        toks = list(prompt) + list(out)
        rows = np.arange(len(prompt) - 1, len(toks) - 1)
        logits = ref.logits_at(weights, rspec, toks, rows)
        pick = out
        if precision != "float32":
            pick = np.asarray(jax.device_get(ref.logits_at(
                weights, rspec, toks, rows, precision).argmax(axis=1)))
        g = np.asarray(jax.device_get(ref.gaps(logits, pick)))
        widest = max(widest, float(g.max()))
        n_tok += len(out)
        agree += int((g == 0).sum())
    return {"widest_logit_gap": widest, "tokens": n_tok,
            "argmax_agree": agree / max(n_tok, 1)}


def judge(weights, cfg: Dict[str, Any], sample, failed: int,
          precision: str = "float32", bench_dir: str = spec.BENCH_DIR
          ) -> Tuple[Dict[str, Any], Dict, bool]:
    """The check of a run: the reference's gaps over the sample, each
    number compared beside its limit, and whether all hold.  With
    ``precision="fp8"`` the control stands in the program's place."""
    got = served_gaps(weights, cfg, sample, precision, bench_dir)
    limit = cfg["limits"]["widest_logit_gap"]
    compared = {"widest_logit_gap": {"value": got["widest_logit_gap"],
                                     "limit": limit},
                "failed_requests": {"value": failed, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    return got, compared, correct


def read_per_layer(cell: spec.Cell, run: Run) -> Dict[str, Any]:
    """Every per-layer metric of the cell.  A metric that the cell lists
    and whose reader finds nothing is a fault of the run, not a metric to
    leave out: it raises."""
    metrics, missing = {}, []
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(run)
        if v is None:
            missing.append(m["name"])
        else:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if missing:
        raise RuntimeError(f"{cell.name}: the trace holds nothing for "
                           f"{missing}")
    return metrics


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t0: float, devices: Optional[list] = None,
             program_hook: Optional[Callable[[Program], None]] = None,
             compile_cache: bool = True,
             control: Optional[str] = None) -> Dict[str, Any]:
    """Set up, measure, check.  Returns the result line's object.  With
    ``control="fp8"`` the check judges the fp8 reference's picks in place
    of the served tokens, through the same comparison."""
    import jax

    from .peaks import peaks_for

    devices = devices or jax.devices()
    if compile_cache:
        log("compile_cache:", enable_compile_cache())
    clock = CompileClock()
    prog = Program(cell.config_name, cell.config, seed, cell.bench_dir)
    mix = spec.traffic_generator(cell.traffic).make(cell.traffic,
                                                    prog.dims.vocab, seed)
    prog.start()
    if program_hook is not None:
        program_hook(prog)
    prog.generate(*prog.warm_prompts(mix.shapes))
    setup_s = time.time() - t0
    log(f"setup_s: {setup_s} (compiles {clock.compiles}, cache hits "
        f"{clock.cache_hits}, compile seconds {clock.seconds})")
    before = clock.compiles
    tr = None
    if trace:
        calls, results, window_s, tr = _traced_window(prog, mix)
    else:
        calls, results, window_s = _window(prog, mix, seconds)
    late = clock.compiles - before
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    attempted = sum(len(p) for p, _ in calls)
    failed = sum(len(t) != m for (_, max_new), res in zip(calls, results)
                 for t, m in zip(res.tokens, max_new))
    log(f"window_s: {window_s}")
    log(f"calls: {len(calls)}")
    log(f"compiles_in_window: {late}")
    log(f"requests: {attempted} failed: {failed}")
    run = Run(cell=cell, dims=prog.dims, chips=cell.chips,
              peaks=peaks_for(devices[0].device_kind) if trace else {},
              results=results, trace=tr, family=prog.family,
              model_shards=prog.model_shards)
    if trace:
        metrics = read_per_layer(cell, run)
    else:
        e2e = {"setup_s": setup_s,
               "output_tok_s": stats.output_tok_s(results, window_s),
               "ttft_p90_s": stats.ttft_p90_s(results),
               "tpot_p90_ms": stats.tpot_p90_ms(results)}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    prog.stop()
    sample = check_sample(calls, run.results, cell.traffic["check_requests"],
                          seed)
    t = time.perf_counter()
    got, compared, correct = judge(prog.weights, cell.config, sample,
                                   failed, control or "float32",
                                   cell.bench_dir)
    log(f"reference_s: {time.perf_counter() - t} over {got['tokens']} "
        f"served tokens of {len(sample)} requests; argmax agrees on "
        f"{got['argmax_agree']}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_mean_s()
        device["window_s"] = tr.window_s
        out["breakdown"] = tr.breakdown()
    for k, c in compared.items():
        log(f"compared {k}: {c['value']} limit {c['limit']}")
    out["compared"] = compared
    return out
