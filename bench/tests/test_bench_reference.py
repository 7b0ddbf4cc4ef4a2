"""The plain float32 reference against the program's served logits, at
test widths on the CPU, for both block variants (SwiGLU with QKV bias,
and GELU without), and the fp8 control against both."""
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench.harness.program import Program
from bench.reference import dense_gqa as ref


def _served_logits(prog, prompt, steps=2):
    """Chunked paged prefill, then ``steps`` decode steps through the
    engine's own jitted programs: the logits of the last one."""
    eng = prog.start()
    V = prog.dims.vocab
    B, maxg = eng.cfg.batch_slots, eng.max_groups
    cache = eng._init_continuous_cache()
    tables = np.zeros((B, maxg), np.int32)
    tables[0] = np.arange(1, maxg + 1)
    chunk = eng.cfg.prefill_chunk
    for s in range(0, len(prompt), chunk):
        piece = jnp.asarray([prompt[s:s + chunk]], jnp.int32)
        logits, cache = eng._slot_chunk_paged(
            prog.weights, {"tokens": piece}, cache, jnp.asarray(tables[0]),
            jnp.asarray(s, jnp.int32), jnp.asarray(0, jnp.int32))
    toks = list(prompt)
    rows = [np.asarray(logits[0, -1, :V])]
    for _ in range(steps):
        toks.append(int(np.argmax(rows[-1])))
        feed = np.zeros((B, 1), np.int32)
        feed[0, 0] = toks[-1]
        lengths = np.zeros(B, np.int32)
        lengths[0] = len(toks) - 1
        logits, cache = eng._decode_multi(
            prog.weights, jnp.asarray(feed), cache, jnp.asarray(lengths),
            jnp.asarray(tables))
        rows.append(np.asarray(logits[0, -1, :V]))
    return toks, np.stack(rows)


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "gelu"])
def test_reference_agrees_with_served_logits(gated):
    cfg = tiny.config(gated)
    prog = Program("t", cfg, seed=3)
    prompt = list(np.random.default_rng(0).integers(1, 512, size=70))
    toks, served = _served_logits(prog, prompt)
    rows = np.arange(len(prompt) - 1, len(toks))
    got = np.asarray(ref.logits_at(prog.weights, ref.Spec.from_config(cfg),
                                   toks, rows, bucket=32))
    assert got.shape == served.shape
    scale = np.abs(got).max()
    # the same float32 arithmetic in another order: rounding only
    assert np.abs(got - served).max() <= 1e-4 * scale
    assert (got.argmax(1) == served.argmax(1)).all()


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "gelu"])
def test_fp8_control_departs_from_the_reference(gated):
    cfg = tiny.config(gated)
    prog = Program("t", cfg, seed=4)
    toks = list(np.random.default_rng(1).integers(1, 512, size=60))
    rows = np.arange(20, 60)
    spec = ref.Spec.from_config(cfg)
    f32 = ref.logits_at(prog.weights, spec, toks, rows, bucket=32)
    fp8 = ref.logits_at(prog.weights, spec, toks, rows, "fp8", bucket=32)
    widest = float(ref.gaps(f32, np.asarray(fp8.argmax(1))).max())
    assert widest > 10 * cfg["limits"]["widest_logit_gap"]


def test_padding_never_reaches_earlier_positions():
    cfg = tiny.config(True)
    prog = Program("t", cfg, seed=5)
    spec = ref.Spec.from_config(cfg)
    toks = list(range(1, 41))
    rows = np.arange(0, 40)
    a = np.asarray(ref.logits_at(prog.weights, spec, toks, rows, bucket=40))
    b = np.asarray(ref.logits_at(prog.weights, spec, toks, rows, bucket=64))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_unknown_precision_is_refused():
    cfg = tiny.config(True)
    prog = Program("t", cfg, seed=5)
    with pytest.raises(ValueError):
        ref.logits_at(prog.weights, ref.Spec.from_config(cfg), [1, 2], [0],
                      precision="int4")
