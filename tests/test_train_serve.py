"""Integration: fault-tolerant training loop + batched serving engine."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ModelConfig, get_config, reduced
from repro.models import Model
from repro.optim import OptimizerConfig
from repro.serve import ServeConfig, ServeEngine
from repro.train import (
    RunKnobs,
    SimulatedFailure,
    TrainLoopConfig,
    train,
)

TINY = ModelConfig(
    name="tiny-lm", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=512, head_dim=16,
    param_dtype="float32", compute_dtype="float32", vocab_pad_multiple=64,
    rope_theta=10_000.0,
)


def _loop(**kw):
    base = dict(
        steps=12, seq_len=32, global_batch=4, log_every=0,
        opt=OptimizerConfig(learning_rate=3e-3, warmup_steps=2,
                            total_steps=50),
        knobs=RunKnobs(rules_preset="dp", remat="none", microbatches=1,
                       loss_chunk=0),
    )
    base.update(kw)
    return TrainLoopConfig(**base)


class TestTrainLoop:
    def test_loss_decreases(self):
        out = train(TINY, _loop(steps=25))
        first = np.mean([h["loss"] for h in out["history"][:5]])
        last = np.mean([h["loss"] for h in out["history"][-5:]])
        assert last < first

    def test_microbatch_equivalence(self):
        """k microbatches must produce (numerically close) identical training."""
        o1 = train(TINY, _loop(steps=5))
        o2 = train(TINY, _loop(steps=5, knobs=RunKnobs(
            rules_preset="dp", remat="none", microbatches=2, loss_chunk=0)))
        l1 = [h["loss"] for h in o1["history"]]
        l2 = [h["loss"] for h in o2["history"]]
        np.testing.assert_allclose(l1, l2, rtol=2e-3, atol=2e-3)

    def test_compression_trains(self):
        out = train(TINY, _loop(steps=20, knobs=RunKnobs(
            rules_preset="dp", remat="none", microbatches=1, loss_chunk=0,
            compression="int8")))
        first = np.mean([h["loss"] for h in out["history"][:5]])
        last = np.mean([h["loss"] for h in out["history"][-5:]])
        assert last < first

    def test_crash_resume_matches_uninterrupted(self, tmp_path):
        """Kill at step 6, resume from the step-5 checkpoint, finish: final
        params must equal an uninterrupted run (deterministic data + ckpt)."""
        straight = train(TINY, _loop(steps=10))

        ckpt = str(tmp_path / "ckpt")
        with pytest.raises(SimulatedFailure):
            train(TINY, _loop(steps=10, ckpt_dir=ckpt, ckpt_every=5,
                              fail_at_step=6))
        resumed = train(TINY, _loop(steps=10, ckpt_dir=ckpt, ckpt_every=5))
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-4, atol=1e-5),
            straight["params"], resumed["params"])

    def test_remat_equivalence(self):
        o1 = train(TINY, _loop(steps=4))
        o2 = train(TINY, _loop(steps=4, knobs=RunKnobs(
            rules_preset="dp", remat="full", microbatches=1, loss_chunk=0)))
        np.testing.assert_allclose(
            [h["loss"] for h in o1["history"]],
            [h["loss"] for h in o2["history"]], rtol=1e-4)


class TestServeEngine:
    @pytest.fixture(scope="class")
    def engine(self):
        model = Model(TINY)
        params = model.init(jax.random.PRNGKey(0))
        return model, params

    def test_greedy_matches_stepwise_forward(self, engine):
        model, params = engine
        eng = ServeEngine(model, params, ServeConfig(max_seq=64,
                                                     batch_slots=2))
        prompts = [[1, 2, 3, 4, 5], [9, 8, 7, 6, 5]]
        res = eng.generate(prompts, max_new_tokens=6)
        assert len(res.tokens) == 2
        assert all(len(t) == 6 for t in res.tokens)
        # oracle: recompute with full forward each step
        for b, prompt in enumerate(prompts):
            seq = list(prompt)
            for _ in range(6):
                batch = {"tokens": jnp.asarray([seq], jnp.int32)}
                hidden, _ = model.forward(params, batch)
                logits = model._logits(params, hidden)[0, -1,
                                                       :TINY.vocab_size]
                seq.append(int(jnp.argmax(logits)))
            assert seq[len(prompt):] == res.tokens[b]

    def test_wave_packing(self, engine):
        model, params = engine
        eng = ServeEngine(model, params, ServeConfig(max_seq=32,
                                                     batch_slots=2))
        res = eng.generate([[1, 2, 3]] * 5, max_new_tokens=3)
        assert len(res.tokens) == 5
        # identical prompts => identical generations
        assert all(t == res.tokens[0] for t in res.tokens)

    def test_eos_early_exit(self, engine):
        model, params = engine
        # discover the first greedy token, then use it as EOS
        eng = ServeEngine(model, params, ServeConfig(max_seq=32,
                                                     batch_slots=1))
        probe = eng.generate([[3, 1, 4]], max_new_tokens=1).tokens[0][0]
        eng_eos = ServeEngine(model, params, ServeConfig(
            max_seq=32, batch_slots=1, eos_token=int(probe)))
        res = eng_eos.generate([[3, 1, 4]], max_new_tokens=8)
        assert res.tokens[0] == [probe]
        assert res.steps <= 2

    def test_unequal_prompts_rejected_by_wave(self, engine):
        """The WAVE runtime keeps its equal-length contract; the default
        continuous runtime is exactly what lifts it."""
        model, params = engine
        eng = ServeEngine(model, params, ServeConfig(max_seq=32,
                                                     runtime="wave"))
        with pytest.raises(ValueError, match="equal-length"):
            eng.generate([[1, 2], [1, 2, 3]], max_new_tokens=2)
        cont = ServeEngine(model, params, ServeConfig(max_seq=32))
        res = cont.generate([[1, 2], [1, 2, 3]], max_new_tokens=2)
        assert [len(t) for t in res.tokens] == [2, 2]

    def test_throughput_metrics(self, engine):
        model, params = engine
        eng = ServeEngine(model, params, ServeConfig(max_seq=32,
                                                     batch_slots=4))
        res = eng.generate([[5, 6, 7]] * 4, max_new_tokens=4)
        assert res.decode_tokens_per_sec > 0
        assert res.prefill_seconds > 0


class TestChunkedPrefill:
    """Runtime chunked prefill: splitting the prompt into prefill_chunk
    segments threaded through the KV cache must be value-exact vs
    whole-prompt prefill — the knob moves *timing*, never tokens."""

    # (prompt_len, prefill_chunk): dividing, non-dividing, chunk == prompt,
    # chunk > prompt, and the degenerate one-token chunk
    PAIRS = [(12, 4), (13, 5), (13, 4), (12, 12), (5, 64), (9, 1)]

    @pytest.fixture(scope="class")
    def engine(self):
        model = Model(TINY)
        params = model.init(jax.random.PRNGKey(0))
        return model, params

    def _prompts(self, plen, n=2):
        rng = np.random.default_rng(plen)
        return rng.integers(1, TINY.vocab_size, size=(n, plen)).tolist()

    @pytest.mark.parametrize("plen,chunk", PAIRS)
    def test_token_parity(self, engine, plen, chunk):
        model, params = engine
        assert model.supports_chunked_prefill
        prompts = self._prompts(plen)
        # wave runtime: the historical whole-wave chunk-count contract
        # (the continuous runtime prefills per slot; see
        # tests/test_continuous_batching.py for its parity pins)
        whole = ServeEngine(model, params, ServeConfig(
            max_seq=64, batch_slots=2, prefill_chunk=2048, runtime="wave"))
        chunked = ServeEngine(model, params, ServeConfig(
            max_seq=64, batch_slots=2, prefill_chunk=chunk, runtime="wave"))
        rw = whole.generate(prompts, max_new_tokens=6)
        rc = chunked.generate(prompts, max_new_tokens=6)
        assert rc.tokens == rw.tokens  # byte-identical continuations
        expect = math.ceil(plen / chunk) if chunk < plen else 1
        assert rc.prefill_chunks == expect  # the knob demonstrably acts
        assert rw.prefill_chunks == 1

    # Whole-prompt prefill attends over the prompt's S keys; the chunked
    # path attends over the whole max_seq cache buffer with a length mask.
    # Same math, but float32 softmax/value reductions of different length
    # round differently (~1-3 ulp of the output's magnitude).  Layer 0's
    # K/V are bit-identical; from layer 1 on, that rounding is carried
    # through the residual stream, the MLP and the next projections.  So
    # the bound is a few tens of float32 ulps of each leaf's largest
    # magnitude, not an elementwise rtol (near-zero entries carry the
    # same absolute error as large ones).
    PARITY_ULPS = 32

    @classmethod
    def _assert_close_ulps(cls, a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        atol = cls.PARITY_ULPS * np.finfo(np.float32).eps * np.abs(a).max()
        np.testing.assert_allclose(a, b, rtol=0, atol=atol)

    @pytest.mark.parametrize("plen,chunk", PAIRS)
    def test_kv_cache_parity(self, engine, plen, chunk):
        """Chunked and whole-prompt prefill leave the same KV caches and
        last-token logits behind, to float32 rounding of the attention
        reductions (layer 0's K/V bit-identical)."""
        model, params = engine
        tok = jnp.asarray(self._prompts(plen), jnp.int32)
        lg_w, cache_w = model.prefill(params, {"tokens": tok},
                                      model.init_cache(2, max_seq=64))
        cache_c = model.init_cache(2, max_seq=64)
        for s in range(0, plen, chunk):
            lg_c, cache_c = model.prefill_chunk(
                params, {"tokens": tok[:, s:s + chunk]}, cache_c)
        assert int(cache_w["index"]) == int(cache_c["index"]) == plen
        self._assert_close_ulps(lg_w, lg_c)
        jax.tree_util.tree_map(self._assert_close_ulps,
                               cache_w["blocks"], cache_c["blocks"])
        # the first layer's K/V come from identical projections of
        # identical embeddings: no reduction of different length yet
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a[0]), np.asarray(b[0])),
            cache_w["blocks"], cache_c["blocks"])

    def test_decode_continues_from_chunked_cache(self, engine):
        """Greedy decode from a chunk-built cache matches the stepwise
        full-forward oracle (chunking is invisible downstream)."""
        model, params = engine
        prompt = [3, 1, 4, 1, 5, 9, 2]
        eng = ServeEngine(model, params, ServeConfig(
            max_seq=32, batch_slots=1, prefill_chunk=3))
        res = eng.generate([prompt], max_new_tokens=4)
        seq = list(prompt)
        for _ in range(4):
            batch = {"tokens": jnp.asarray([seq], jnp.int32)}
            hidden, _ = model.forward(params, batch)
            logits = model._logits(params, hidden)[0, -1, :TINY.vocab_size]
            seq.append(int(jnp.argmax(logits)))
        assert seq[len(prompt):] == res.tokens[0]

    def test_live_serve_sut_measures_real_engine(self):
        """LiveServeSUT: a test builds the real engine under the candidate
        knobs and wall-clocks it — metrics carry the chunk count, so a
        tuned prefill_chunk is visible in the provenance."""
        from repro.serve.space import LiveServeSUT

        model = Model(TINY)
        params = model.init(jax.random.PRNGKey(0))
        sut = LiveServeSUT(model, params,
                           base=ServeConfig(max_seq=32),
                           prompt_len=9, gen_len=4, n_requests=2,
                           warmup=1, repeats=1, max_slots=2)
        space = sut.space()
        cfg = space.default_config()
        cfg["prefill_chunk"] = 4  # non-dividing: 9 tokens -> 3 chunks
        cfg["max_batch"] = 2
        m = sut.test(cfg)
        assert m.higher_is_better and m.value > 0
        assert m.metrics["latency_s"] > 0
        # continuous runtime: per-request prefill => 2 requests x 3 chunks
        assert m.metrics["prefill_chunks"] == 6
        assert m.metrics["prefill_s"] > 0

    def test_train_step_sut_measures_real_step(self):
        """TrainStepSUT: re-jits the real train step under the knobs and
        wall-clocks the microbatch loop (median-of-repeats timing)."""
        from repro.core.sut_jax import TrainStepSUT

        sut = TrainStepSUT(TINY, seq_len=16, global_batch=4, steps=1,
                           warmup=1, repeats=1)
        space = sut.space()
        cfg = space.default_config()
        cfg["microbatches"] = 2
        m = sut.test(cfg)
        assert m.higher_is_better and m.value > 0
        assert m.metrics["step_seconds"] > 0
        assert np.isfinite(m.metrics["loss"])

    def test_unsupported_stack_falls_back_to_whole_prefill(self):
        """Models whose blocks cannot append multi-token segments exactly
        (recurrent mixers) prefill whole prompts regardless of the knob."""
        cfg = reduced(get_config("zamba2-1.2b"))
        model = Model(cfg)
        assert not model.supports_chunked_prefill
        params = model.init(jax.random.PRNGKey(0))
        eng = ServeEngine(model, params, ServeConfig(
            max_seq=32, batch_slots=1, prefill_chunk=2))
        res = eng.generate([[1, 2, 3, 4, 5]], max_new_tokens=2)
        assert res.prefill_chunks == 1  # one whole-prompt dispatch
        assert len(res.tokens[0]) == 2

    def test_frontend_model_chunked_parity_and_validation(self):
        """Frontend/encoder models: generate() without embeds fails loudly
        on BOTH prefill paths (the chunked path would otherwise silently
        attend to zero memory), and with embeds the first chunk carries
        them so chunked == whole-prompt tokens."""
        cfg = reduced(get_config("llama-3.2-vision-90b"))
        model = Model(cfg)
        assert model.supports_chunked_prefill
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = rng.integers(1, cfg.vocab_size, size=(2, 9)).tolist()
        fe = rng.normal(size=(2, cfg.frontend_tokens,
                              cfg.frontend_dim)).astype(np.float32)
        chunked = ServeEngine(model, params, ServeConfig(
            max_seq=32, batch_slots=2, prefill_chunk=4, runtime="wave"))
        with pytest.raises(ValueError, match="frontend"):
            chunked.generate(prompts, max_new_tokens=2)
        whole = ServeEngine(model, params, ServeConfig(
            max_seq=32, batch_slots=2, prefill_chunk=2048, runtime="wave"))
        with pytest.raises(ValueError, match="frontend"):
            whole.generate(prompts, max_new_tokens=2)
        rw = whole.generate(prompts, max_new_tokens=3, frontend_embeds=fe)
        rc = chunked.generate(prompts, max_new_tokens=3,
                              frontend_embeds=fe)
        assert rc.tokens == rw.tokens
        assert rc.prefill_chunks == 3  # ceil(9 / 4)

    def test_capacity_bound_moe_is_not_chunkable(self):
        """Capacity-bound MoE routing drops tokens per routing GROUP, and
        the grouping differs between whole-prompt and per-chunk prefill —
        chunking such a stack would change generated tokens, so the gate
        must refuse it.  Drop-free capacity (cf*K >= E, what ``reduced``
        configs use) keeps MoE chunk-exact and allowed."""
        base = reduced(get_config("grok-1-314b"))
        assert base.moe is not None
        # reduced() picks drop-free capacity: chunking is exact -> allowed
        assert (base.moe.capacity_factor * base.moe.experts_per_token
                >= base.moe.n_experts)
        assert Model(base).supports_chunked_prefill
        # a production-style capacity factor (tokens get dropped) -> gated
        bound = dataclasses.replace(
            base, moe=dataclasses.replace(base.moe, capacity_factor=1.0))
        assert not Model(bound).supports_chunked_prefill
