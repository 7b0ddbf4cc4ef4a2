"""Continuous-batching runtime: schedule/layout parity, paging invariants,
per-request provenance, and surrogate↔runtime rank agreement.

The central contract: the tuned knobs (`schedule`, `kv_cache_pages`,
`prefill_chunk`, `max_batch`) move *when* work happens — never *what* is
generated.  Every request's tokens must be identical across schedules,
KV layouts and slot placements, pinned here at token level against the
wave runtime's stepwise-forward oracle lineage."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ModelConfig
from repro.models import Model
from repro.serve import ServeConfig, ServeEngine
from repro.serve.paging import PAGE_TOKENS

TINY = ModelConfig(
    name="tiny-lm", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=512, head_dim=16,
    param_dtype="float32", compute_dtype="float32", vocab_pad_multiple=64,
    rope_theta=10_000.0,
)

PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7, 6, 5], [2, 2, 2],
           [7, 1, 4, 1, 5, 9, 2, 6], [3, 3], [5, 4, 3, 2, 1, 6]]
MAX_NEW = [6, 3, 5, 2, 7, 4]


@pytest.fixture(scope="module")
def engine():
    model = Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _cfg(**kw):
    base = dict(max_seq=32, batch_slots=2, runtime="continuous",
                prefill_chunk=4)
    base.update(kw)
    return ServeConfig(**base)


@pytest.fixture(scope="module")
def reference_tokens(engine):
    """Oracle continuations: wave runtime, one request per wave."""
    model, params = engine
    eng = ServeEngine(model, params, ServeConfig(
        max_seq=32, batch_slots=1, runtime="wave"))
    return [eng.generate([p], m).tokens[0]
            for p, m in zip(PROMPTS, MAX_NEW)]


class TestScheduleParity:
    @pytest.mark.parametrize("layout", ["dense", "paged"])
    def test_tokens_identical_across_schedules(self, engine, layout,
                                               reference_tokens):
        model, params = engine
        outs = {}
        for sched in ("fifo", "sjf", "interleave"):
            eng = ServeEngine(model, params,
                              _cfg(kv_layout=layout, schedule=sched))
            outs[sched] = eng.generate(PROMPTS, MAX_NEW).tokens
        assert outs["fifo"] == outs["sjf"] == outs["interleave"]
        # ... and identical to the one-request-per-wave oracle: admission
        # order, slot placement and pool layout never touch token values
        assert outs["fifo"] == reference_tokens

    def test_paged_vs_dense_parity(self, engine):
        model, params = engine
        dense = ServeEngine(model, params, _cfg(kv_layout="dense"))
        paged = ServeEngine(model, params, _cfg(kv_layout="paged"))
        assert dense.generate(PROMPTS, MAX_NEW).tokens == \
            paged.generate(PROMPTS, MAX_NEW).tokens

    def test_slot_count_invariance(self, engine, reference_tokens):
        """More slots change concurrency, not content."""
        model, params = engine
        for slots in (1, 3):
            eng = ServeEngine(model, params, _cfg(batch_slots=slots,
                                                  kv_layout="paged"))
            assert eng.generate(PROMPTS, MAX_NEW).tokens == reference_tokens

    def test_eos_frees_slot_early(self, engine):
        model, params = engine
        probe = ServeEngine(model, params, _cfg(batch_slots=1))
        eos = probe.generate([[3, 1, 4]], 1).tokens[0][0]
        eng = ServeEngine(model, params, _cfg(
            batch_slots=1, eos_token=int(eos)))
        res = eng.generate([[3, 1, 4], [1, 2, 3, 4]], [8, 2])
        assert res.tokens[0] == [eos]
        assert len(res.tokens[1]) == 2


class TestPagingRuntime:
    def test_no_page_leaks_after_mixed_run(self, engine):
        model, params = engine
        eng = ServeEngine(model, params, _cfg(kv_layout="paged",
                                              batch_slots=3))
        eng.generate(PROMPTS, MAX_NEW)
        alloc = eng.last_alloc
        assert alloc is not None
        assert alloc.groups_in_use == 0  # every completion released
        assert alloc.high_water > 0
        alloc.check_balanced()

    def test_small_pool_bounds_concurrency_not_tokens(self, engine,
                                                      reference_tokens):
        """A pool big enough for ~one request serializes admission (the
        real memory/throughput trade-off) but generates the same tokens —
        and needs more decode steps at the same token count (occupancy
        collapses: the noise-free throughput signal)."""
        model, params = engine
        small = ServeEngine(model, params, _cfg(
            kv_layout="paged", batch_slots=3, kv_cache_pages=3))
        big = ServeEngine(model, params, _cfg(
            kv_layout="paged", batch_slots=3))
        rs, rb = (e.generate(PROMPTS, MAX_NEW) for e in (small, big))
        assert rs.tokens == rb.tokens == reference_tokens
        assert sum(len(t) for t in rs.tokens) == sum(len(t) for t in rb.tokens)
        assert rs.steps > rb.steps
        assert small.last_alloc.high_water <= 2

    def test_undersized_pool_rejected_at_config(self):
        with pytest.raises(ValueError, match="KV cache too small"):
            ServeConfig(max_seq=64, runtime="continuous", kv_layout="paged",
                        kv_cache_pages=2)

    def test_unknown_runtime_and_layout_rejected(self):
        with pytest.raises(ValueError, match="unknown runtime"):
            ServeConfig(runtime="batch")
        with pytest.raises(ValueError, match="unknown kv_layout"):
            ServeConfig(kv_layout="ring")

    def test_grouped_pool_layout(self, engine, reference_tokens):
        """kv_page_block > 1 (the paged kernel's pages_per_block tile as
        allocator granularity) coarsens groups without touching tokens."""
        model, params = engine
        eng = ServeEngine(model, params, _cfg(
            kv_layout="paged", kv_page_block=2))
        assert eng.group_tokens == 2 * PAGE_TOKENS
        assert eng.generate(PROMPTS, MAX_NEW).tokens == reference_tokens

    def test_recurrent_stack_falls_back_to_wave(self):
        from repro.configs import get_config, reduced

        cfg = reduced(get_config("zamba2-1.2b"))
        model = Model(cfg)
        assert not model.supports_continuous_batching
        params = model.init(jax.random.PRNGKey(0))
        eng = ServeEngine(model, params, ServeConfig(
            max_seq=32, batch_slots=1, runtime="continuous"))
        assert not eng._continuous
        res = eng.generate([[1, 2, 3, 4, 5]], 2)
        assert len(res.tokens[0]) == 2


class TestPagePolicy:
    """The tuned page_policy axis: on_demand admits on prompt-size
    reservations, grows them per step, and preempts (recompute) on pool
    exhaustion — identical tokens, strictly better packing on
    oversubscribed pools."""

    # decode-heavy mixed workload: worst-case footprints of 2 groups per
    # request at PAGE_TOKENS=16, so a 4-page pool (3 usable groups)
    # serializes reserve admission but packs 3 on_demand prompts
    HEAVY_PROMPTS = [[1, 2, 3], [9, 8, 7, 6], [2, 2, 2, 2, 2],
                     [7, 1, 4, 1], [3, 3, 3], [5, 4, 3, 2, 1, 6]]
    HEAVY_NEW = [14, 12, 16, 13, 18, 12]

    def _run(self, engine, policy, pages=4, slots=3, **kw):
        model, params = engine
        eng = ServeEngine(model, params, _cfg(
            kv_layout="paged", batch_slots=slots, kv_cache_pages=pages,
            page_policy=policy, **kw))
        res = eng.generate(self.HEAVY_PROMPTS, self.HEAVY_NEW)
        eng.last_alloc.check_balanced()
        assert eng.last_alloc.groups_in_use == 0
        return res

    def test_forced_preemption_token_parity(self, engine):
        """Preemption re-prefills prompt+generated and continues at the
        same (rid, token-index) keys: bit-identical tokens, fewer decode
        steps (better packing) on the oversubscribed pool."""
        reserve = self._run(engine, "reserve")
        on_demand = self._run(engine, "on_demand")
        assert on_demand.preemptions > 0  # the pool really ran dry
        assert reserve.preemptions == 0   # reserve can never preempt
        assert on_demand.tokens == reserve.tokens
        assert on_demand.steps < reserve.steps
        # per-request provenance carries the recompute count
        assert sum(r["preemptions"] for r in on_demand.per_request) \
            == on_demand.preemptions

    def test_decode_tokens_exclude_prefill_tokens(self, engine,
                                                  monkeypatch):
        """Every admission's first token (a readmission's too) comes from
        its prefill's logits; each decode step gives one token per active
        slot, which the ``serve.dispatch`` span's ``active`` counts."""
        import contextlib

        import repro.serve.engine as engine_mod

        active = []

        def span(name, **ids):
            if name == "serve.dispatch":
                active.append(ids["active"])
            return contextlib.nullcontext()

        monkeypatch.setattr(engine_mod, "span", span)
        res = self._run(engine, "on_demand")
        assert res.preemptions > 0 and len(active) == res.steps
        tokens = sum(len(t) for t in res.tokens)
        assert sum(active) == tokens - len(res.tokens) - res.preemptions
        assert res.decode_tokens_per_sec == pytest.approx(
            sum(active) / res.decode_seconds)

    def test_policy_parity_across_schedules(self, engine):
        outs = [self._run(engine, "on_demand", schedule=s).tokens
                for s in ("fifo", "sjf", "interleave")]
        assert outs[0] == outs[1] == outs[2]

    def test_on_demand_temperature_parity(self, engine):
        """Sampled tokens survive preemption bit-identically: the
        (rid, token-index) key stream is recomputed, not resumed."""
        outs = {}
        for pol in ("reserve", "on_demand"):
            outs[pol] = self._run(engine, pol, temperature=0.8, seed=7)
        assert outs["on_demand"].preemptions > 0
        assert outs["on_demand"].tokens == outs["reserve"].tokens

    def test_on_demand_inert_on_big_pools(self, engine):
        """With every worst case resident the policies are identical:
        no extension failures, no preemptions, same step count."""
        reserve = self._run(engine, "reserve", pages=16)
        on_demand = self._run(engine, "on_demand", pages=16)
        assert on_demand.preemptions == 0
        assert on_demand.tokens == reserve.tokens
        assert on_demand.steps == reserve.steps

    def test_dense_layout_ignores_policy(self, engine):
        model, params = engine
        eng = ServeEngine(model, params, _cfg(kv_layout="dense",
                                              page_policy="on_demand"))
        res = eng.generate(self.HEAVY_PROMPTS, self.HEAVY_NEW)
        assert res.preemptions == 0

    def test_unknown_page_policy_rejected(self):
        with pytest.raises(ValueError, match="unknown page_policy"):
            ServeConfig(page_policy="lazy")

    def test_error_path_releases_pages(self, engine):
        """Regression: an exception mid-generation (e.g. inside a decode
        dispatch) must unwind every live reservation — a stranded page
        group would silently shrink every later run's pool."""
        model, params = engine
        for policy in ("reserve", "on_demand"):
            eng = ServeEngine(model, params, _cfg(
                kv_layout="paged", batch_slots=3, page_policy=policy))

            calls = {"n": 0}
            real = eng._decode_multi

            def boom(*a, _real=real, **kw):
                calls["n"] += 1
                if calls["n"] >= 3:  # fail mid-flight, with live slots
                    raise RuntimeError("injected decode failure")
                return _real(*a, **kw)

            eng._decode_multi = boom
            with pytest.raises(RuntimeError, match="injected"):
                eng.generate(self.HEAVY_PROMPTS, self.HEAVY_NEW)
            assert eng.last_alloc is not None
            assert eng.last_alloc.groups_in_use == 0
            eng.last_alloc.check_balanced()

    def test_sjf_bypass_beats_head_of_line_blocking(self, engine):
        """A blocked sjf head (reservation too big for the free pool)
        must not starve a smaller pending request that fits: the bounded
        bypass admits it, so it starts BEFORE the policy-earlier blocked
        request.  fifo stays strict (arrival order of first tokens)."""
        model, params = engine
        prompts = [[1, 2, 3, 4], [9, 8, 7, 6, 5], [2, 4, 6, 8, 1, 3]]
        max_new = [28, 27, 6]  # worst-case groups: 2, 2, 1 (4-page pool)
        ttft = {}
        for sched in ("sjf", "fifo"):
            eng = ServeEngine(model, params, _cfg(
                kv_layout="paged", batch_slots=2, kv_cache_pages=4,
                schedule=sched))
            res = eng.generate(prompts, max_new)
            ttft[sched] = [r["ttft_s"] for r in res.per_request]
        # sjf: rid 2 bypasses blocked rid 1 and decodes alongside rid 0
        assert ttft["sjf"][2] < ttft["sjf"][1]
        # fifo keeps strict admission order
        assert ttft["fifo"][1] < ttft["fifo"][2]


class TestPerRequestStats:
    def test_provenance_shape_and_ordering(self, engine):
        model, params = engine
        eng = ServeEngine(model, params, _cfg(kv_layout="paged"))
        res = eng.generate(PROMPTS, MAX_NEW)
        assert [r["rid"] for r in res.per_request] == list(range(len(PROMPTS)))
        for r, p, m, t in zip(res.per_request, PROMPTS, MAX_NEW, res.tokens):
            assert r["prompt_len"] == len(p)
            assert r["new_tokens"] == len(t) <= m
            assert 0 < r["ttft_s"] <= r["latency_s"]
        assert res.p50_latency_s <= res.p95_latency_s
        assert res.decode_tokens_per_sec > 0

    def test_admit_stamp_precedes_first_token(self, engine):
        """``admit_s`` is the first admission to a slot, from the call's
        start: fifo over two slots seats rids 0 and 1 at once, and rid 2
        only after one of them has finished."""
        model, params = engine
        for layout in ("dense", "paged"):
            res = ServeEngine(model, params, _cfg(kv_layout=layout)
                              ).generate(PROMPTS, MAX_NEW)
            for r in res.per_request:
                assert 0 <= r["admit_s"] <= r["ttft_s"] <= r["latency_s"]
            admits = [r["admit_s"] for r in res.per_request]
            assert admits == sorted(admits)
            assert admits[2] >= min(r["latency_s"]
                                    for r in res.per_request[:2])

    def test_decode_tokens_per_sec_counts_decode_steps_only(self, engine):
        model, params = engine
        res = ServeEngine(model, params, _cfg(kv_layout="paged")).generate(
            PROMPTS, MAX_NEW)
        assert [len(t) for t in res.tokens] == MAX_NEW
        # 27 tokens; each request's first comes from its prefill
        assert res.decode_tokens_per_sec == pytest.approx(
            (sum(MAX_NEW) - len(PROMPTS)) / res.decode_seconds)

    def test_admission_blocked_counts_pool_refusals(self, engine):
        """A pool that holds about one reservation refuses admission while
        slots are free, before some decode steps; an ample pool and dense
        buffers never refuse."""
        model, params = engine
        small, big, dense = (ServeEngine(model, params, _cfg(
            kv_layout=layout, batch_slots=3, **kw)).generate(
                PROMPTS, MAX_NEW) for layout, kw in (
                    ("paged", {"kv_cache_pages": 3}), ("paged", {}),
                    ("dense", {})))
        assert 0 < small.admission_blocked <= small.steps
        assert big.admission_blocked == dense.admission_blocked == 0

    def test_wave_runtime_also_reports(self, engine):
        model, params = engine
        eng = ServeEngine(model, params, ServeConfig(
            max_seq=32, batch_slots=2, runtime="wave"))
        res = eng.generate([[1, 2, 3]] * 5, 3)
        assert len(res.per_request) == 5
        assert all(r["latency_s"] > 0 for r in res.per_request)
        assert all(0 <= r["admit_s"] <= r["ttft_s"] for r in res.per_request)
        # wave w+1 finishes after wave w
        lats = [r["latency_s"] for r in res.per_request]
        assert lats == sorted(lats)

    def test_temperature_sampling_schedule_invariant(self, engine):
        """Sampled (non-greedy) tokens key on (request id, token index)
        only, so they too are identical across schedules."""
        model, params = engine
        outs = {}
        for sched in ("fifo", "sjf"):
            eng = ServeEngine(model, params, _cfg(
                schedule=sched, temperature=0.8, seed=7))
            outs[sched] = eng.generate(PROMPTS, MAX_NEW).tokens
        assert outs["fifo"] == outs["sjf"]


class TestSurrogateRankAgreement:
    """Satellite: the analytic surrogate's schedule/paging terms are
    re-derived from the real scheduler; pin that both rank configs the
    same way, on the runtime's noise-free counters where possible."""

    def _surrogate(self, schedule, pages, p=None, policy="reserve"):
        from repro.serve.space import (CotuneParams, coupled_serve_metrics,
                                       serve_knob_space)

        p = p or CotuneParams(prompt_len=64, gen_len=16, max_seq=256,
                              n_requests=16)
        cfg = serve_knob_space(p.max_seq).default_config()
        cfg["schedule"] = schedule
        cfg["kv_cache_pages"] = pages
        cfg["page_policy"] = policy
        kcfg = p.default_kernel_config()
        return coupled_serve_metrics(cfg, kcfg, p)

    def test_pages_rank_agreement(self, engine):
        """Fewer pages => fewer resident requests => lower throughput.
        Engine evidence: decode-step count at equal tokens (deterministic);
        surrogate evidence: the value ordering."""
        model, params = engine
        steps = {}
        for pages in (3, 8):
            eng = ServeEngine(model, params, _cfg(
                kv_layout="paged", batch_slots=3, kv_cache_pages=pages))
            steps[pages] = eng.generate(PROMPTS, MAX_NEW).steps
        assert steps[3] > steps[8]  # engine: small pool => low occupancy
        lo = self._surrogate("fifo", pages=2)
        hi = self._surrogate("fifo", pages=16)
        assert lo.value < hi.value  # surrogate ranks the same way
        assert lo.metrics["resident"] < hi.metrics["resident"]

    def test_page_policy_rank_agreement(self, engine):
        """Engine evidence (noise-free decode-step counts, pinned above in
        TestPagePolicy): on_demand completes equal tokens in fewer steps
        on an oversubscribed pool.  The surrogate must rank the same way —
        and flip on big pools, where on_demand only pays bookkeeping: the
        policy optimum genuinely shifts with kv_cache_pages."""
        from repro.serve.space import CotuneParams

        # decode-heavy workload: expected footprint (prompt+gen/2) is
        # well under the worst case, which is where on_demand packs
        p = CotuneParams(prompt_len=32, gen_len=96, max_seq=256,
                         n_requests=16)
        small_od = self._surrogate("fifo", pages=21, p=p, policy="on_demand")
        small_rs = self._surrogate("fifo", pages=21, p=p, policy="reserve")
        assert small_od.value > small_rs.value
        assert small_od.metrics["resident"] > small_rs.metrics["resident"]
        assert small_od.metrics["preempt_frac"] > 0  # the recompute tax
        big_od = self._surrogate("fifo", pages=256, p=p, policy="on_demand")
        big_rs = self._surrogate("fifo", pages=256, p=p, policy="reserve")
        assert big_rs.value > big_od.value  # bookkeeping, no packing gain
        assert big_od.metrics["preempt_frac"] == 0
        assert big_od.metrics["resident"] == big_rs.metrics["resident"]

    def test_sjf_rank_agreement_on_mean_latency(self, engine):
        """One long prompt ahead of short ones on a single slot: sjf must
        cut MEAN latency vs fifo in the real engine, as the surrogate's
        sjf term claims.  Throughput (total tokens/steps) stays equal."""
        model, params = engine
        prompts = [[7] * 24] + [[i + 1, 2, 3] for i in range(4)]
        max_new = [4] * 5
        res = {}
        for sched in ("fifo", "sjf"):
            eng = ServeEngine(model, params, ServeConfig(
                max_seq=32, batch_slots=1, runtime="continuous",
                schedule=sched, prefill_chunk=8))
            res[sched] = eng.generate(prompts, max_new)
        mean = {s: np.mean([r["latency_s"] for r in res[s].per_request])
                for s in res}
        assert mean["sjf"] < mean["fifo"]
        assert res["sjf"].steps == res["fifo"].steps
        s_f = self._surrogate("fifo", pages=64)
        s_s = self._surrogate("sjf", pages=64)
        assert s_s.metrics["latency_s"] < s_f.metrics["latency_s"]
        assert s_s.metrics["raw_throughput"] == s_f.metrics["raw_throughput"]

    def test_interleave_rank_agreement_on_overlap(self, engine):
        """Interleave keeps decoding while admissions prefill: the engine
        must issue decode steps *between* a long admission's chunks (fifo
        cannot), matching the surrogate's overlapped-prefill term."""
        model, params = engine
        prompts = [[1, 2, 3], [9] * 24]
        max_new = [12, 2]
        steps = {}
        for sched in ("fifo", "interleave"):
            eng = ServeEngine(model, params, ServeConfig(
                max_seq=32, batch_slots=2, runtime="continuous",
                schedule=sched, prefill_chunk=4))
            steps[sched] = eng.generate(prompts, max_new)
        assert steps["interleave"].tokens == steps["fifo"].tokens
        s_f = self._surrogate("fifo", pages=64)
        s_i = self._surrogate("interleave", pages=64)
        # at C>1 the surrogate charges prefill once (overlapped) instead of
        # per-admission: interleave >= fifo on raw throughput
        assert s_i.metrics["raw_throughput"] >= s_f.metrics["raw_throughput"]


# 18-token common prefix (one full 16-token page group) + distinct tails:
# the repeated-system-prompt workload prefix sharing exists for.
SHARED_PREFIX = [7, 3, 9, 1, 4, 4, 8, 2, 6, 5, 1, 9, 2, 7, 3, 8, 5, 2]
SHARED_PROMPTS = [SHARED_PREFIX + [11], SHARED_PREFIX + [12, 13],
                  SHARED_PREFIX + [14, 15, 16], SHARED_PREFIX + [17]]
SHARED_NEW = [5, 4, 6, 3]


class TestPrefixSharing:
    """The CoW prefix-sharing tentpole: identical tokens, fewer prefill
    dispatches, zero page leaks — under forced copy-on-write splits and
    sharer preemptions."""

    def _run(self, engine, share, max_new=None, **kw):
        model, params = engine
        eng = ServeEngine(model, params, _cfg(
            max_seq=64, kv_layout="paged", share_prefix=share, **kw))
        res = eng.generate(SHARED_PROMPTS, max_new or SHARED_NEW)
        eng.last_alloc.check_balanced()
        assert eng.last_alloc.groups_in_use == 0
        return res

    def test_sharing_token_parity_and_fewer_prefill_chunks(self, engine):
        off = self._run(engine, False)
        on = self._run(engine, True)
        assert on.tokens == off.tokens  # sharing moves work, not content
        assert on.shared_prefix_tokens > 0
        assert off.shared_prefix_tokens == 0
        # the shared groups' prefill was genuinely skipped
        assert on.prefill_chunks < off.prefill_chunks
        # per-request provenance carries the shared-token counts
        assert sum(r["shared_tokens"] for r in on.per_request) \
            == on.shared_prefix_tokens
        assert any(r["shared_tokens"] == 0 for r in on.per_request)  # donor

    def test_sharing_parity_across_schedules(self, engine):
        outs = [self._run(engine, True, schedule=s).tokens
                for s in ("fifo", "sjf", "interleave")]
        assert outs[0] == outs[1] == outs[2]

    def test_sharing_temperature_parity(self, engine):
        """Sampled tokens key on (rid, token index) only — admitting from
        shared groups must not shift the key stream."""
        off = self._run(engine, False, temperature=0.8, seed=7)
        on = self._run(engine, True, temperature=0.8, seed=7)
        assert on.tokens == off.tokens
        assert on.shared_prefix_tokens > 0

    def test_forced_cow_split_preserves_tokens(self, engine):
        """An identical prompt and a boundary-sharing shorter prompt both
        cover into their final token's group: the engine must CoW-split
        that group before the first divergent write, leaving the donor's
        KV bytes untouched (pinned via the donor's own continuation)."""
        model, params = engine
        donor = [((i * 37) % 509) + 1 for i in range(32)]  # 2 full groups
        # the donor decodes long enough to stay resident (groups live,
        # registry fresh) while the filler drains a slot and each sharer
        # is admitted in turn; both sharers' coverage ends mid-group
        # (identical prompt: 31 of 32 — the last token always dispatches
        # for logits; boundary prompt: 19 of 20), forcing a CoW split
        prompts = [donor, [1, 2, 3], list(donor), donor[:20]]
        max_new = [26, 2, 5, 4]
        outs = {}
        for share in (False, True):
            eng = ServeEngine(model, params, _cfg(
                max_seq=64, batch_slots=2, kv_layout="paged",
                share_prefix=share))
            outs[share] = eng.generate(prompts, max_new)
            eng.last_alloc.check_balanced()
            assert eng.last_alloc.groups_in_use == 0
        assert outs[True].tokens == outs[False].tokens
        assert outs[True].cow_splits >= 2  # both sharers forced a split
        assert outs[True].shared_prefix_tokens > 0
        assert outs[True].prefill_chunks < outs[False].prefill_chunks

    def test_sharing_survives_preemption_and_cuts_recompute(self, engine):
        """on_demand exhaustion on a shared workload: shared groups stay
        resident through a sharer's preemption (other owners hold them),
        so readmission re-prefills only the private tail — same tokens,
        fewer prefill dispatches than the unshared run."""
        outs = {}
        for share in (False, True):
            # decode-heavy on a 4-usable-group pool: requests outgrow
            # their prompt-size reservations mid-decode and run it dry
            # even with the shared prefix deduplicated
            outs[share] = self._run(engine, share, batch_slots=3,
                                    kv_cache_pages=5,
                                    page_policy="on_demand",
                                    max_new=[14, 13, 16, 12])
        assert outs[True].tokens == outs[False].tokens
        assert outs[True].preemptions > 0  # the pool really ran dry
        assert outs[True].prefill_chunks < outs[False].prefill_chunks

    def test_sharing_inert_on_dense_layout(self, engine):
        model, params = engine
        eng = ServeEngine(model, params, _cfg(
            max_seq=64, kv_layout="dense", share_prefix=True))
        res = eng.generate(SHARED_PROMPTS, SHARED_NEW)
        assert res.shared_prefix_tokens == 0 and res.cow_splits == 0


class TestSpeculativeDecode:
    """Self-speculative n-gram decoding: the draft rides the SAME verify
    dispatch and the acceptance rule replays greedy/sampled choices at
    the same (rid, token-index) keys — so tokens are bit-identical at any
    draft_len, and repetitive histories collapse dispatch counts."""

    def test_draft_parity_matrix(self, engine, reference_tokens):
        model, params = engine
        for k in (2, 4):
            for sched in ("fifo", "sjf", "interleave"):
                eng = ServeEngine(model, params, _cfg(
                    kv_layout="paged", schedule=sched, draft_len=k))
                res = eng.generate(PROMPTS, MAX_NEW)
                assert res.tokens == reference_tokens, (k, sched)

    def test_draft_parity_dense_layout(self, engine, reference_tokens):
        model, params = engine
        eng = ServeEngine(model, params, _cfg(kv_layout="dense",
                                              draft_len=4))
        assert eng.generate(PROMPTS, MAX_NEW).tokens == reference_tokens

    def test_draft_parity_under_preemption(self, engine):
        """Speculation composes with on_demand growth/preemption: the
        draft-aware pre-extension and recompute keep token parity."""
        model, params = engine
        outs = {}
        for k in (0, 4):
            eng = ServeEngine(model, params, _cfg(
                kv_layout="paged", batch_slots=3, kv_cache_pages=4,
                page_policy="on_demand", draft_len=k))
            outs[k] = eng.generate(TestPagePolicy.HEAVY_PROMPTS,
                                   TestPagePolicy.HEAVY_NEW)
            eng.last_alloc.check_balanced()
            assert eng.last_alloc.groups_in_use == 0
        assert outs[4].tokens == outs[0].tokens
        assert outs[4].preemptions > 0

    def test_draft_temperature_parity(self, engine):
        model, params = engine
        outs = {}
        for k in (0, 4):
            eng = ServeEngine(model, params, _cfg(
                kv_layout="paged", temperature=0.8, seed=7, draft_len=k))
            outs[k] = eng.generate(PROMPTS, MAX_NEW).tokens
        assert outs[4] == outs[0]

    def test_acceptance_collapses_dispatches_on_repetitive_history(
            self, engine):
        """The acceptance machinery itself, pinned on a constant-output
        model (zeroed params -> uniform logits -> greedy repeats token 0):
        the n-gram draft matches the generated loop, verification accepts
        it, and equal tokens arrive in strictly fewer dispatches."""
        model, params = engine
        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        runs = {}
        for k in (0, 4):
            eng = ServeEngine(model, zero, _cfg(kv_layout="paged",
                                                draft_len=k))
            runs[k] = eng.generate([[5, 3, 5, 3]], 12)
        assert runs[4].tokens == runs[0].tokens
        assert runs[4].drafted > 0
        assert runs[4].accepted > 0
        assert runs[4].steps < runs[0].steps
        assert 0.0 < runs[4].acceptance_rate <= 1.0
        assert runs[0].drafted == runs[0].accepted == 0

    def test_sharing_and_speculation_compose(self, engine):
        model, params = engine
        outs = {}
        for on in (False, True):
            eng = ServeEngine(model, params, _cfg(
                max_seq=64, kv_layout="paged", share_prefix=on,
                draft_len=4 if on else 0))
            outs[on] = eng.generate(SHARED_PROMPTS, SHARED_NEW)
            eng.last_alloc.check_balanced()
            assert eng.last_alloc.groups_in_use == 0
        assert outs[True].tokens == outs[False].tokens
        assert outs[True].shared_prefix_tokens > 0

    def test_negative_draft_len_rejected(self):
        with pytest.raises(ValueError, match="draft_len"):
            _cfg(draft_len=-1)

    def test_new_knob_surrogate_rank_agreement(self, engine):
        """Engine evidence (prefill_chunks / dispatch counts above) says
        sharing and accepted speculation do strictly less work for equal
        tokens; the surrogate must rank the widened knob space the same
        way — and must rank speculation WORSE when nothing is accepted."""
        from repro.serve.space import CotuneParams, coupled_serve_metrics

        p = CotuneParams(prompt_len=64, gen_len=16, max_seq=256,
                         n_requests=16)
        kcfg = p.default_kernel_config()
        base = dict(max_batch=8, prefill_chunk=64, kv_cache_pages=64,
                    schedule="fifo", page_policy="reserve")
        v0 = coupled_serve_metrics(dict(base), kcfg, p)
        vs = coupled_serve_metrics(dict(base, share_prefix=1), kcfg, p)
        vk = coupled_serve_metrics(dict(base, draft_len=4), kcfg, p)
        assert vs.value > v0.value
        assert vs.metrics["prefill_s"] < v0.metrics["prefill_s"]
        assert vk.value > v0.value
        assert vk.metrics["spec_tokens_per_step"] > 1.0
        # zero acceptance: drafts are pure verify overhead
        p_dry = CotuneParams(prompt_len=64, gen_len=16, max_seq=256,
                             n_requests=16, spec_accept=0.0)
        vk_dry = coupled_serve_metrics(dict(base, draft_len=4), kcfg, p_dry)
        v0_dry = coupled_serve_metrics(dict(base), kcfg, p_dry)
        assert vk_dry.value < v0_dry.value
