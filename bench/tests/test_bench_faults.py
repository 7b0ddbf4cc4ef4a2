"""A whole run without the look for a chip, at test size on the CPU: the
sound program comes out correct, and each fault a serving cell can have,
planted in the timed path, comes out not correct."""
import time

import pytest

import tiny
from bench.harness import runner


def _run(hook=None, gated=True, seed=2 ** 31 + 5):
    return runner.run_cell(tiny.cell(gated), seed, 0.0, False, time.time(),
                           program_hook=hook, compile_cache=False)


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "gelu"])
def test_sound_program_is_correct(gated):
    out = _run(gated=gated)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] == 6
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"setup_s", "output_tok_s", "ttft_p90_s",
                                   "tpot_p90_ms"}
    c = out["compared"]["widest_logit_gap"]
    assert 0 <= c["value"] <= c["limit"]


def _alter_tokens(prog):
    """A token altered where it is produced: every decode step's greedy
    pick moved to the next vocabulary id."""
    eng = prog.engine
    orig = eng._argmax_multi
    vocab = prog.dims.vocab
    eng._argmax_multi = lambda logits: (orig(logits) + 1) % vocab


def _stale_state(prog):
    """A step that returns its state unchanged: decode steps whose KV
    writes are dropped, so later steps attend a stale cache."""
    eng = prog.engine
    orig = eng._decode_multi

    def step(params, tokens, cache, lengths, tables):
        logits, _ = orig(params, tokens, cache, lengths, tables)
        return logits, cache

    eng._decode_multi = step


def _drop_request_tokens(prog):
    """An answer cut short: every request returns one token fewer than
    it asked for."""
    eng = prog.engine
    orig = eng.generate

    def generate(prompts, max_new):
        res = orig(prompts, max_new)
        res.tokens = [t[:-1] for t in res.tokens]
        return res

    eng.generate = generate


@pytest.mark.parametrize("fault", [_alter_tokens, _stale_state,
                                   _drop_request_tokens],
                         ids=["token_altered", "state_unchanged",
                              "answer_cut_short"])
def test_fault_is_not_correct(fault):
    out = _run(fault)
    assert out["correct"] is False
    assert list(out)[-1] == "compared"
