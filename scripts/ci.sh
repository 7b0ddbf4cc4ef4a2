#!/usr/bin/env bash
# CI entry point: tier-1 test suite + batched-tuning smoke benchmark.
#
#   scripts/ci.sh            # full tier-1 suite, then the smoke bench
#   scripts/ci.sh --fast     # skip the slow subprocess/dry-run tests
#
# The smoke benchmark runs the batched-vs-sequential evaluation engine
# comparison (RRS on the MySQL surrogate, budget 500) and fails CI if the
# engines diverge; its speedup line is the perf-trajectory signal.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

PYTEST_ARGS=(-x -q)
if [[ "${1:-}" == "--fast" ]]; then
    PYTEST_ARGS+=(-k "not subprocess and not DryRun and not TuneCLI and not collectives_counted")
fi

# Collected-count floor: CI fails if the collected count ever drops below it
# (a silently skipped/broken test file must not read as green).
MIN_COLLECTED=703
echo "=== check: collected test count >= ${MIN_COLLECTED} ==="
COLLECT_OUT=$(python -m pytest -q --collect-only 2>&1 | tail -5 || true)
COLLECTED=$(tail -1 <<<"$COLLECT_OUT" | grep -oE '^[0-9]+' || true)
echo "collected: ${COLLECTED:-<collection failed>}"
if [[ -z "$COLLECTED" ]] || (( COLLECTED < MIN_COLLECTED )); then
    echo "$COLLECT_OUT"
    echo "FAIL: test collection below the ${MIN_COLLECTED} baseline (or broken)"
    exit 1
fi

echo "=== tier-1: python -m pytest ${PYTEST_ARGS[*]} ==="
python -m pytest "${PYTEST_ARGS[@]}"

echo "=== determinism matrix: every optimizer × dispatch mode × seed ==="
python -m pytest -q tests/test_determinism_matrix.py

echo "=== lint gate: jit/Pallas/allocator + interprocedural dataflow ==="
# Machine-readable AST lint over the whole package (repro.analysis.lint):
# jit retrace hazards, pallas_call arity contracts, allocator unwind
# discipline, plus the PR 10 dataflow families — determinism-taint,
# jit-trace-capture/host-effect, cache lock-discipline — run over the
# module-level call graph.  Exits non-zero on ANY finding; the committed
# baseline is zero, so a new finding is a regression, not noise.
python -m repro.analysis.lint --check src/repro
echo "lint gate OK (zero findings)"

echo "=== smoke: dataflow lint recall (planted fixtures must fire) ==="
# Zero findings on src/repro must mean "analyzed and clean", not
# "analysis silently off": each PR 10 rule family must fire on its
# planted fixture (7 taint + 3 capture + 2 host-effect + 3 lock = 15)
# and the pragma fixture must stay silent.  lint_bench re-times the full
# gate and writes BENCH_lint.json (wall-time per pass + planted recall).
timeout 120 python - <<'EOF'
from pathlib import Path

from repro.analysis import lint as L

FIX = Path("tests/fixtures/lint")
want = {
    "bad_taint.py": {"determinism-taint": 7},
    "bad_trace_capture.py": {"jit-trace-capture": 3, "jit-host-effect": 2},
    "bad_cache_lock.py": {"cache-lock-discipline": 3},
}
for name, expect in want.items():
    got = {}
    for f in L.lint_file(FIX / name):
        got[f.rule] = got.get(f.rule, 0) + 1
    assert got == expect, f"{name}: planted {expect}, lint saw {got}"
assert L.lint_file(FIX / "pragma_ok.py") == [], "pragmas stopped working"
print("dataflow recall smoke OK (15 planted findings caught, pragmas ok)")
EOF
timeout 120 python -m benchmarks.lint_bench --check

echo "=== smoke: static feasibility pruning (zero-budget infeasible) ==="
# A kernel tune over a shape whose biggest tiles blow VMEM: infeasible
# configs must be pruned WITHOUT charging budget (counted instead), every
# charged trial must be statically feasible and finitely scored, and the
# pruned trial stream must reproduce under its seed.
timeout 60 python - <<'EOF'
import math

from repro.analysis.feasibility import kernel_feasibility
from repro.autotune.sut import KernelSUT
from repro.core.tuner import Tuner

DIMS = {"ROWS": 8192, "D": 6144}  # block_rows >= 512 exceeds VMEM

def run():
    sut = KernelSUT("rmsnorm", DIMS, mode="model")
    return Tuner(sut.space(), sut, budget=24, optimizer="rrs",
                 seed=0).run()

rep, rep2 = run(), run()
model = kernel_feasibility("rmsnorm", DIMS, "float32")
assert rep.n_infeasible_pruned > 0, "pruning never engaged"
assert all(model(t.config) for t in rep.history[1:]), \
    "an infeasible config was charged a test"
assert all(math.isfinite(t.value) for t in rep.history[1:]), \
    "a charged trial scored inf"
trace = lambda r: [(sorted(t.config.items()), t.value) for t in r.history]
assert trace(rep) == trace(rep2) \
    and rep.n_infeasible_pruned == rep2.n_infeasible_pruned, \
    "pruning broke seeded determinism"
print(f"pruning smoke OK ({rep.n_infeasible_pruned} pruned for free, "
      f"{rep.n_tests} charged, best={rep.best_config})")
EOF

echo "=== smoke: batched tuning engine (budget 500, ~seconds) ==="
timeout 30 python - <<'EOF'
import benchmarks.rrs_convergence as rc

rows = rc._bench_batched_engine()
for name, us, derived in rows:
    print(f"{name},{us:.1f},{derived}")
speedup = float(rows[2][2].rstrip("x"))
assert speedup > 1.0, f"batched engine slower than sequential ({speedup}x)"
EOF

echo "=== smoke: joint co-tuning (--joint, tiny budget, surrogate) ==="
CI_TMP="$(mktemp -d)"
trap 'rm -rf "$CI_TMP"' EXIT
REPRO_AUTOTUNE_CACHE="$CI_TMP/autotune.json" timeout 30 \
    python -m repro.launch.tune --arch xlstm-350m --shape decode_32k \
    --joint --surrogate --budget 16 --out-dir "$CI_TMP/tune" > /dev/null
echo "joint smoke OK"

echo "=== smoke: LIVE joint co-tuning (--joint --real, tiny model, ~30s) ==="
# Wall-clocks the real ServeEngine + train step per trial (reduced
# gemma-7b, budget 4, single timed repeat) and must persist all three
# winners — kernel, serve_engine, train_step — in one cache file.
REPRO_AUTOTUNE_CACHE="$CI_TMP/autotune_real.json" timeout 90 \
    python -m repro.launch.tune --arch gemma-7b --shape decode_32k \
    --joint --real --budget 4 --real-repeats 1 \
    --out-dir "$CI_TMP/tune_real" > /dev/null
python - "$CI_TMP/autotune_real.json" <<'EOF'
import json, sys

systems = {k.split("|")[1] for k in json.load(open(sys.argv[1]))}
missing = {"decode_attention", "serve_engine", "train_step"} - systems
assert not missing, f"cache missing joint winners: {missing}"
print("real joint smoke OK (kernel + serve_engine + train_step persisted)")
EOF

echo "=== check: joint >= independent tuning at equal budget ==="
timeout 120 python -m benchmarks.cotune_bench --check

echo "=== smoke: continuous batching (3 schedules x paged+dense, ~30s) ==="
# Mixed-length workload through the REAL continuous engine under every
# schedule and both KV layouts; per-request tokens must be identical
# everywhere (the schedule knob moves timing, never content) and the
# paged allocator must end balanced.
timeout 120 python - <<'EOF'
import jax, numpy as np
from repro.configs import ModelConfig
from repro.models import Model
from repro.serve import ServeConfig, ServeEngine

cfg = ModelConfig(
    name="ci-tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=512, head_dim=16,
    param_dtype="float32", compute_dtype="float32", vocab_pad_multiple=64,
    rope_theta=10_000.0)
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
prompts = [rng.integers(1, 512, size=n).tolist()
           for n in rng.integers(2, 20, size=10)]
gens = [int(g) for g in rng.integers(1, 9, size=10)]
ref = None
for layout in ("paged", "dense"):
    for sched in ("fifo", "sjf", "interleave"):
        eng = ServeEngine(model, params, ServeConfig(
            max_seq=32, batch_slots=3, runtime="continuous",
            kv_layout=layout, schedule=sched, prefill_chunk=4))
        res = eng.generate(prompts, gens)
        if ref is None:
            ref = res.tokens
        assert res.tokens == ref, f"{layout}/{sched} diverged"
        if layout == "paged":
            assert eng.last_alloc.groups_in_use == 0, "page leak"
            eng.last_alloc.check_balanced()
print("continuous smoke OK (6 runtime combos, identical tokens, no leaks)")
EOF

echo "=== smoke: oversubscription + recompute preemption (~20s) ==="
# Decode-heavy workload on a pool too small for worst-case reservations:
# on_demand MUST preempt (recompute), tokens must match the reserve run
# bit-for-bit, and no page group may outlive the run.
timeout 120 python - <<'EOF'
import jax, numpy as np
from repro.configs import ModelConfig
from repro.models import Model
from repro.serve import ServeConfig, ServeEngine

cfg = ModelConfig(
    name="ci-tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=512, head_dim=16,
    param_dtype="float32", compute_dtype="float32", vocab_pad_multiple=64,
    rope_theta=10_000.0)
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(1)
prompts = [rng.integers(1, 512, size=n).tolist()
           for n in rng.integers(3, 9, size=8)]
gens = [int(g) for g in rng.integers(10, 17, size=8)]
out = {}
for policy in ("reserve", "on_demand"):
    eng = ServeEngine(model, params, ServeConfig(
        max_seq=32, batch_slots=3, runtime="continuous", kv_layout="paged",
        kv_cache_pages=4, page_policy=policy, prefill_chunk=4))
    res = eng.generate(prompts, gens)
    assert eng.last_alloc.groups_in_use == 0, f"{policy}: page leak"
    eng.last_alloc.check_balanced()
    out[policy] = res
assert out["on_demand"].preemptions > 0, "tiny pool never preempted"
assert out["reserve"].preemptions == 0
assert out["on_demand"].tokens == out["reserve"].tokens, \
    "preemption changed generated tokens"
assert out["on_demand"].steps < out["reserve"].steps, \
    "on_demand packing did not reduce decode steps"
print(f"oversubscription smoke OK ({out['on_demand'].preemptions} "
      f"preemptions, identical tokens, "
      f"{out['on_demand'].steps} vs {out['reserve'].steps} decode steps, "
      "no leaks)")
EOF

echo "=== smoke: prefix sharing (CoW) + speculative decoding (~30s) ==="
# Repeated shared-prefix workload: sharing MUST skip prefill dispatches
# and split at least one group copy-on-write; speculation MUST draft and
# accept tokens in fewer decode dispatches. Tokens are bit-identical to
# the plain run in every arm, and no page group may outlive a run.
timeout 120 python - <<'EOF'
import jax, jax.numpy as jnp
from repro.configs import ModelConfig
from repro.models import Model
from repro.serve import ServeConfig, ServeEngine

cfg = ModelConfig(
    name="ci-tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=512, head_dim=16,
    param_dtype="float32", compute_dtype="float32", vocab_pad_multiple=64,
    rope_theta=10_000.0)
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
# A long donor, a short filler that frees a slot, then two sharers: an
# exact copy (coverage capped at prompt-1 lands mid-group -> CoW) and a
# mid-group prefix. The donor generates long enough to stay resident.
donor = [((i * 37) % 509) + 1 for i in range(32)]
prompts = [donor, [1, 2, 3], list(donor), donor[:20]]
gens = [26, 2, 5, 4]

def run(p, **kw):
    eng = ServeEngine(model, p, ServeConfig(
        max_seq=64, batch_slots=2, runtime="continuous",
        kv_layout="paged", prefill_chunk=4, **kw))
    res = eng.generate(prompts, gens)
    assert eng.last_alloc.groups_in_use == 0, f"{kw}: page leak"
    eng.last_alloc.check_balanced()
    return res

plain = run(params)
shared = run(params, share_prefix=True)
assert shared.tokens == plain.tokens, "sharing changed generated tokens"
assert shared.shared_prefix_tokens > 0, "shared-prefix workload never shared"
assert shared.cow_splits > 0, "no copy-on-write split ever happened"
assert shared.prefill_chunks < plain.prefill_chunks, \
    "sharing did not skip prefill dispatches"
both = run(params, share_prefix=True, draft_len=4)
assert both.tokens == plain.tokens, "sharing+speculation changed tokens"
# Zeroed params give repetitive argmax output, so n-gram drafts MUST
# land: fewer decode dispatches for the same (trivial) tokens.
zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
zplain = run(zeros)
zspec = run(zeros, draft_len=4)
assert zspec.tokens == zplain.tokens, "speculation changed generated tokens"
assert zspec.drafted > 0 and zspec.accepted > 0, "speculation never accepted"
assert zspec.steps < zplain.steps, \
    "accepted drafts did not reduce decode dispatches"
print(f"sharing+speculation smoke OK ({shared.shared_prefix_tokens} shared "
      f"tokens, {shared.cow_splits} CoW splits, "
      f"{shared.prefill_chunks} vs {plain.prefill_chunks} prefill chunks, "
      f"{zspec.accepted}/{zspec.drafted} drafts accepted, "
      f"{zspec.steps} vs {zplain.steps} decode dispatches, identical "
      "tokens, no leaks)")
EOF

echo "=== smoke: online workload-aware retuning (~30s) ==="
# A drifting workload (distinct long prompts, then shared-prefix short
# tails) through the live engine with --retune semantics: the shift
# detector MUST fire exactly once, the mid-run knob swap MUST leave
# generated tokens bit-identical to a never-retuned run, the measured
# draft acceptance MUST reach the retune's surrogate (spec_accept within
# 0.1), and the winner MUST persist under its workload signature.
REPRO_AUTOTUNE_CACHE="$CI_TMP/retune_smoke.json" timeout 120 python - <<'EOF'
import math

import jax, numpy as np
from repro import autotune
from repro.configs import ModelConfig
from repro.models import Model
from repro.serve import ServeConfig, ServeEngine
from repro.serve.workload import fingerprint_sig

cfg = ModelConfig(
    name="ci-tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=512, head_dim=16,
    param_dtype="float32", compute_dtype="float32", vocab_pad_multiple=64,
    rope_theta=10_000.0)
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
BASE = dict(max_seq=48, batch_slots=8, kv_layout="paged", seed=0,
            prefill_chunk=8, slot_cap=3)
RETUNE = dict(retune=True, retune_budget=8, retune_threshold=0.3,
              retune_window=10, retune_cooldown=200,
              retune_check_every=2, retune_min_requests=6)

# the signature the deployed knobs were (notionally) tuned under:
# measured from a phase-A-only run with the detector anchored but inert
rng = np.random.default_rng(0)
pa = [rng.integers(1, 500, size=20).tolist() for _ in range(6)]
eng = ServeEngine(model, params, ServeConfig(
    **BASE, retune=True, retune_threshold=10.0, retune_min_requests=6,
    retune_window=10))
eng.generate(pa, [12] * 6)
sig_a = fingerprint_sig(eng.last_retuner.baseline)

# phase A then a shift to shared-prefix short-tail bursts
rng = np.random.default_rng(0)
prompts = [rng.integers(1, 500, size=20).tolist() for _ in range(3)]
shared = rng.integers(1, 500, size=32).tolist()
prompts += [shared + rng.integers(1, 500, size=3).tolist()
            for _ in range(12)]
gens = [12] * 3 + [6] * 12

autotune.reset_default_cache()
eng = ServeEngine(model, params, ServeConfig(
    **BASE, tuned_signature=sig_a, **RETUNE))
res = eng.generate(prompts, gens)
eng.last_alloc.check_balanced()
base = ServeEngine(model, params,
                   ServeConfig(**BASE)).generate(prompts, gens)
assert len(res.retunes) == 1, f"retune fired {len(res.retunes)}x, not once"
ev = res.retunes[0]
assert ev["applied"], "the retune moved no knob"
assert res.tokens == base.tokens, "knob swap changed generated tokens"
assert math.isfinite(ev["measured_accept"]) and \
    abs(ev["spec_accept"] - ev["measured_accept"]) <= 0.1, \
    "measured acceptance never reached the retune surrogate"
cands = autotune.serve_config_candidates(
    {"S": 48, "H": cfg.padded_heads, "KV": cfg.n_kv_heads,
     "D": cfg.head_dim_}, cfg.compute_dtype)
entry = cands.get(ev["signature"])
assert entry is not None, "winner not cached under its workload signature"
assert entry["config"] == ev["config"]
assert entry["meta"]["source"] == "online_retune"
moved = ", ".join(f"{k} {o}->{n}" for k, (o, n) in ev["applied"].items())
print(f"retune smoke OK (drift {ev['distance']:.2f} @step {ev['step']} "
      f"[{ev['warm_source']}] -> {moved}; accept "
      f"{ev['measured_accept']:.2f}, identical tokens, winner cached)")
EOF

echo "=== smoke: sharded serving (8 fake devices, TP + replicas, ~60s) ==="
# Tensor-parallel decode over a (data, model) mesh: per-request tokens
# must be bit-identical across meshes, pure TP must dispatch EXACTLY the
# unsharded number of batched decode steps (it splits each dispatch, it
# never adds one), a data axis must strictly cut them (capacity widens
# x data), and the paged pool must end balanced.  Runs in its own
# interpreter so XLA_FLAGS can fake 8 host devices before jax loads —
# this is also the only sharded-engine coverage in --fast runs, which
# skip the subprocess tier-1 tests.
XLA_FLAGS="--xla_force_host_platform_device_count=8" timeout 180 python - <<'EOF'
import jax, numpy as np
from repro.configs import ModelConfig
from repro.models import Model
from repro.serve import ServeConfig, ServeEngine

assert len(jax.devices()) == 8, jax.devices()
cfg = ModelConfig(
    name="ci-tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=512, head_dim=16,
    param_dtype="float32", compute_dtype="float32", vocab_pad_multiple=64,
    rope_theta=10_000.0)
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
prompts = [rng.integers(1, 512, size=n).tolist()
           for n in rng.integers(2, 20, size=10)]
gens = [int(g) for g in rng.integers(1, 9, size=10)]

def run(mesh):
    eng = ServeEngine(model, params, ServeConfig(
        max_seq=32, batch_slots=3, runtime="continuous", kv_layout="paged",
        prefill_chunk=4, mesh_shape=mesh))
    res = eng.generate(prompts, gens)
    assert eng.last_alloc.groups_in_use == 0, f"{mesh}: page leak"
    eng.last_alloc.check_balanced()
    return res

base = run(None)
tp, rep, grid = run((1, 2)), run((4, 1)), run((2, 2))
for name, r in (("tp 1x2", tp), ("replicas 4x1", rep), ("grid 2x2", grid)):
    assert r.tokens == base.tokens, f"{name}: tokens diverged"
assert tp.steps == base.steps, "pure TP changed the dispatch count"
assert rep.steps < base.steps, "replica widening cut no decode steps"
assert grid.steps < base.steps, "grid data axis cut no decode steps"
print(f"sharded smoke OK (tokens identical on 1x2/4x1/2x2; TP steps "
      f"{tp.steps}=={base.steps}, replicas {rep.steps}<{base.steps}, "
      "no leaks)")
EOF

echo "=== smoke: sharded joint tuning (--max-devices 8, mesh-keyed winner) ==="
# The widened serve subspace (mesh_devices / tp_vs_replicas / rules
# preset) through the real --joint path: the tuned winner must be
# deployable AND persist under its mesh topology key, never under the
# single-device key.
REPRO_AUTOTUNE_CACHE="$CI_TMP/autotune_sharded.json" timeout 90 \
    python -m repro.launch.tune --arch xlstm-350m --shape decode_32k \
    --joint --surrogate --budget 16 --max-devices 8 \
    --out-dir "$CI_TMP/tune_sharded" > /dev/null
python - "$CI_TMP/autotune_sharded.json" <<'EOF'
import json, re, sys

keys = [k for k in json.load(open(sys.argv[1]))
        if k.split("|")[1] == "serve_engine"]
assert keys, "no serve_engine winner persisted"
mesh_keys = [k for k in keys if re.search(r"\|d\d+m\d+$", k)]
assert mesh_keys, f"serve winner not mesh-keyed: {keys}"
print(f"sharded joint smoke OK (serve winner cached under "
      f"{mesh_keys[0].split('|')[-1]})")
EOF

echo "=== check: continuous+paged >= wave; on_demand >= reserve; shared >= 2x;"
echo "===        online retune >= 1.15x stale winner; sharded parity ==="
timeout 450 python -m benchmarks.serve_bench --check

echo "CI OK"
