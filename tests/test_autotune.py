"""Kernel autotune subsystem: spaces, cost model, cache, kernel threading."""
import json
import logging
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro import autotune
from repro.autotune import (
    AutotuneCache,
    KERNELS,
    KernelSUT,
    KernelSpace,
    shape_sig,
)

FA_DIMS = {"B": 1, "S": 256, "SK": 256, "H": 4, "KV": 2, "D": 32}


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    autotune.reset_default_cache()
    yield path
    autotune.reset_default_cache()


class TestKernelSpace:
    @pytest.mark.parametrize("kernel", sorted(KERNELS))
    def test_spaces_are_valid(self, kernel):
        ks = KernelSpace(kernel)
        space = ks.space()
        assert space.dim >= 1
        cfg = space.default_config()
        space.validate(cfg)
        assert set(cfg) == set(ks.knobs)

    def test_missing_dims_rejected(self):
        with pytest.raises(ValueError, match="missing dims"):
            KernelSpace("flash_attention").validate_dims({"B": 1})

    def test_unknown_kernel_rejected(self):
        with pytest.raises(ValueError, match="unknown kernel"):
            KernelSpace("conv3d")

    def test_sig_is_canonical(self):
        assert shape_sig({"S": 256, "B": 1}) == shape_sig({"B": 1, "S": 256})


class TestCostModel:
    @pytest.mark.parametrize("kernel,dims", [
        ("flash_attention", FA_DIMS),
        ("flash_attention", dict(FA_DIMS, SK=1024)),  # cache-prefill shape
        ("flash_attention", dict(FA_DIMS, SK=64)),    # cross-attn, SK < S
        ("decode_attention", FA_DIMS),
        ("gla", {"B": 1, "S": 256, "H": 2, "DK": 32, "DV": 32}),
        ("rmsnorm", {"ROWS": 1024, "D": 512}),
        ("paged_attention", {"B": 32, "S": 4096, "H": 40, "KV": 8,
                             "D": 128}),
    ])
    def test_model_finite_and_positive(self, kernel, dims):
        d = KERNELS[kernel]
        space = d.make_space()
        for cfg in [space.default_config(),
                    space.from_unit_vector(np.full(space.dim, 0.01)),
                    space.from_unit_vector(np.full(space.dim, 0.97))]:
            cost = d.model_cost(cfg, dims, "float32")
            assert cost > 0

    @pytest.mark.parametrize("ppb,n", [(1, 32), (4, 8), (32, 1)])
    def test_paged_model_follows_the_kernel_tiling(self, ppb, n):
        """Qwen2.5-32B decode on one chip: a grid step streams n whole
        page groups of all 8 KV heads, double-buffered, so the footprint
        is 2 x (K, V) x n x KV x T x D plus the f32 softmax state and the
        query and output blocks; steps are B x ceil(MAXG / n)."""
        from repro.autotune.space import _pa_tiling

        d = KERNELS["paged_attention"]
        dims = {"B": 32, "S": 4096, "H": 40, "KV": 8, "D": 128}
        cfg = {"pages_per_block": ppb}
        T, maxg, got = _pa_tiling(cfg, dims, "bfloat16")
        assert (T, maxg, got) == (16 * ppb, 4096 // (16 * ppb), n)
        blocks = 2 * 2 * n * 8 * T * 128 * 2
        vmem = d.vmem_footprint(cfg, dims, "bfloat16")
        assert blocks < vmem < blocks + 2**20
        assert d.model_cost(cfg, dims, "bfloat16") < float("inf")

    def test_vmem_overflow_is_infeasible(self):
        d = KERNELS["flash_attention"]
        big = {"B": 1, "S": 1 << 20, "SK": 1 << 20, "H": 1, "KV": 1,
               "D": 4096}
        cost = d.model_cost({"block_q": 512, "block_kv": 512}, big,
                            "float32")
        assert cost == float("inf")


class TestCacheRoundTrip:
    def test_tune_persist_reload_same_blocks(self, tmp_cache):
        """The acceptance criterion: tune → persist → reload → same blocks
        under interpret mode on CPU."""
        res = autotune.autotune_kernel("flash_attention", FA_DIMS,
                                       budget=12, interpret=True, seed=0)
        assert res["mode"] == "model"  # interpret => deterministic model
        assert os.path.exists(tmp_cache)
        # a brand-new cache object re-reads the file from disk
        fresh = AutotuneCache(tmp_cache)
        got = autotune.cached_blocks("flash_attention", FA_DIMS, "float32",
                                     cache=fresh)
        assert got == res["config"]
        # and the default-cache path (what ops.py uses) agrees
        autotune.reset_default_cache()
        assert autotune.cached_blocks("flash_attention", FA_DIMS,
                                      "float32") == res["config"]

    def test_ensure_tuned_is_idempotent(self, tmp_cache):
        first = autotune.ensure_tuned("rmsnorm", {"ROWS": 512, "D": 128},
                                      budget=8, interpret=True)
        blob = json.load(open(tmp_cache))
        second = autotune.ensure_tuned("rmsnorm", {"ROWS": 512, "D": 128},
                                      budget=8, interpret=True)
        assert first == second
        assert json.load(open(tmp_cache)) == blob  # no re-tune, no rewrite

    def test_entries_keyed_by_shape_and_dtype(self, tmp_cache):
        autotune.autotune_kernel("rmsnorm", {"ROWS": 512, "D": 128},
                                 budget=6, interpret=True)
        autotune.autotune_kernel("rmsnorm", {"ROWS": 2048, "D": 128},
                                 budget=6, interpret=True)
        cache = AutotuneCache(tmp_cache)
        assert len(cache) == 2
        assert autotune.cached_blocks("rmsnorm", {"ROWS": 512, "D": 128},
                                      "bfloat16", cache=cache) is None


class TestKernelSUTTiming:
    def test_time_mode_measures(self):
        sut = KernelSUT("rmsnorm", {"ROWS": 64, "D": 32}, mode="time",
                        interpret=True, timing_iters=1)
        m = sut.test({"block_rows": 16})
        assert m.value > 0 and not m.higher_is_better
        assert m.metrics["mode"] == "time"

    @pytest.mark.parametrize("kernel,dims,config", [
        ("rmsnorm", {"ROWS": 64, "D": 32}, {"block_rows": 16}),
        ("paged_attention", {"B": 2, "S": 64, "H": 4, "KV": 2, "D": 16},
         {"pages_per_block": 2}),
    ])
    def test_timed_runs_do_not_compile(self, kernel, dims, config):
        """The timed repeats run one compiled program: compiles per
        ``test`` do not grow with ``timing_iters`` (an eager kernel call
        compiles its Pallas kernel again on every call)."""
        import jax

        compiles = []

        def count(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        # warm the eager helper ops (input draws, index arrays) first
        KernelSUT(kernel, dims, mode="time", interpret=True,
                  timing_iters=1).test(config)
        jax.monitoring.register_event_duration_secs_listener(count)
        try:
            per_test = []
            for iters in (1, 4):
                sut = KernelSUT(kernel, dims, mode="time", interpret=True,
                                timing_iters=iters)
                before = len(compiles)
                sut.test(config)
                per_test.append(len(compiles) - before)
        finally:
            jax.monitoring.unregister_event_duration_listener(count)
        assert per_test[0] == per_test[1], per_test


class TestKernelThreading:
    """Block overrides flow from the cache through the public entry points."""

    def test_ops_consult_cache_and_stay_correct(self, tmp_cache):
        from repro.kernels import ops
        from repro.kernels.ref import attention_ref, rmsnorm_ref

        # seed the cache with a deliberately non-default (but valid) tiling
        cache = autotune.default_cache()
        cache.put("rmsnorm", shape_sig({"ROWS": 8, "D": 32}), "float32",
                  autotune.backend_name(), {"block_rows": 8}, 1.0)
        dims = {"B": 1, "S": 64, "SK": 64, "H": 2, "KV": 2, "D": 16}
        cache.put("flash_attention", shape_sig(dims), "float32",
                  autotune.backend_name(),
                  {"block_q": 16, "block_kv": 32}, 1.0)

        # dim_semantics rides along (builtin default when never tuned)
        resolved = ops._resolve("rmsnorm", {"ROWS": 8, "D": 32},
                                "float32", {"block_rows": None})
        assert resolved == {"block_rows": 8, "dim_semantics": "parallel"}
        resolved = ops._resolve("flash_attention", dims, "float32",
                                {"block_q": None, "block_kv": None})
        assert resolved == {"block_q": 16, "block_kv": 32,
                            "dim_semantics": "parallel"}
        # explicit overrides always win over the cache
        resolved = ops._resolve("flash_attention", dims, "float32",
                                {"block_q": 64, "block_kv": None})
        assert resolved == {"block_q": 64, "block_kv": 32,
                            "dim_semantics": "parallel"}

        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float32)
        out = ops.flash_attention(q, k, v)  # tuned blocks picked up
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(attention_ref(q, k, v)),
            rtol=2e-5, atol=2e-5)
        x = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
        s = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(ops.rmsnorm(x, s)),
            np.asarray(rmsnorm_ref(x, s)), rtol=2e-5, atol=2e-5)

    def test_pallas_entry_points_accept_overrides(self):
        from repro.kernels.decode_attention import flash_decode_pallas
        from repro.kernels.gla import gla_pallas
        from repro.kernels.ref import attention_ref, gla_ref

        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(2, 4, 8)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 48, 2, 8)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 48, 2, 8)), jnp.float32)
        for bkv in (8, 16, 48):
            out = flash_decode_pallas(q, k, v, 48, block_kv=bkv,
                                      interpret=True)
            ref = attention_ref(q[:, None], k, v, causal=False)[:, 0]
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=3e-5, atol=3e-5)
        gq = jnp.asarray(rng.normal(size=(1, 32, 1, 8)), jnp.float32)
        gg = jnp.asarray(-np.abs(rng.normal(size=(1, 32, 1)) * 0.3),
                         jnp.float32)
        for chunk in (8, 16):
            y, _ = gla_pallas(gq, gq, gq, gg, chunk=chunk, interpret=True)
            yr, _ = gla_ref(gq, gq, gq, gg)
            np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                       rtol=5e-5, atol=5e-5)


class TestKVSeqLenInSignature:
    """Regression: the flash_attention cache key must include the KV
    sequence length (SK).  Before the fix the key used only the KV *head
    count*, so cross-attention / cache-prefill problems with different KV
    lengths collided on one entry."""

    def test_distinct_keys_for_differing_kv_lengths(self, tmp_cache):
        cache = autotune.default_cache()
        cache.put("flash_attention", shape_sig(FA_DIMS), "float32",
                  autotune.backend_name(), {"block_q": 32, "block_kv": 32},
                  1.0)
        same = autotune.cached_blocks("flash_attention", FA_DIMS, "float32")
        assert same == {"block_q": 32, "block_kv": 32}
        # same query length, longer KV stream: a DIFFERENT problem
        longer = dict(FA_DIMS, SK=512)
        assert autotune.cached_blocks("flash_attention", longer,
                                      "float32") is None
        assert shape_sig(FA_DIMS) != shape_sig(longer)

    def test_ops_resolve_keys_on_kv_length(self, tmp_cache):
        from repro.kernels import ops

        cache = autotune.default_cache()
        self_attn = {"B": 1, "S": 64, "SK": 64, "H": 2, "KV": 2, "D": 16}
        cache.put("flash_attention", shape_sig(self_attn), "float32",
                  autotune.backend_name(), {"block_q": 16, "block_kv": 16},
                  1.0)
        hit = ops._resolve("flash_attention", self_attn, "float32",
                           {"block_q": None, "block_kv": None})
        assert hit == {"block_q": 16, "block_kv": 16,
                       "dim_semantics": "parallel"}
        # cache-prefill shape (same S, longer SK) must NOT inherit it;
        # it falls back to the builtin defaults
        prefill = dict(self_attn, SK=128)
        miss = ops._resolve("flash_attention", prefill, "float32",
                            {"block_q": None, "block_kv": None})
        assert miss == ops.DEFAULT_BLOCKS["flash_attention"]

    def test_sk_required_in_dims(self):
        with pytest.raises(ValueError, match="missing dims"):
            KernelSpace("flash_attention").validate_dims(
                {"B": 1, "S": 256, "H": 4, "KV": 2, "D": 32})

    def test_cost_model_distinguishes_kv_length(self):
        d = KERNELS["flash_attention"]
        cfg = {"block_q": 64, "block_kv": 64}
        short = d.model_cost(cfg, FA_DIMS, "float32")
        long_ = d.model_cost(cfg, dict(FA_DIMS, SK=4096), "float32")
        assert long_ > short  # more KV to stream must cost more


class TestCacheSchemaVersion:
    """Regression: the SK fix invalidates pre-SK entries via a key schema
    bump — old keys can never resolve and are dropped on rewrite."""

    def test_keys_are_versioned(self):
        key = AutotuneCache.key("flash_attention", "sig", "float32", "cpu")
        assert key.startswith(f"v{autotune.SCHEMA_VERSION}|")

    def test_old_schema_entries_invalidated(self, tmp_cache):
        stale = {
            # v1 (unversioned) key: flash_attention signature without SK
            "flash_attention|B1_D32_H4_KV2_S256|float32|cpu": {
                "config": {"block_q": 999, "block_kv": 999},
                "value": 1.0, "meta": {}, "time": 0.0},
        }
        with open(tmp_cache, "w") as f:
            json.dump(stale, f)
        cache = AutotuneCache(tmp_cache)
        assert autotune.cached_blocks("flash_attention", FA_DIMS,
                                      "float32", cache=cache) is None
        # a write rewrites the file without the stale entry
        cache.put("rmsnorm", shape_sig({"ROWS": 8, "D": 32}), "float32",
                  "cpu", {"block_rows": 8}, 1.0)
        on_disk = json.load(open(tmp_cache))
        assert all(k.startswith(f"v{autotune.SCHEMA_VERSION}|")
                   for k in on_disk)

    def test_newer_schema_entries_survive(self, tmp_cache):
        """A shared cache file touched by a NEWER binary must not lose that
        binary's entries when this version writes — only older schemas are
        invalidated."""
        future = f"v{autotune.SCHEMA_VERSION + 1}|rmsnorm|D32_ROWS8" \
                 "|float32|tpu"
        with open(tmp_cache, "w") as f:
            json.dump({future: {"config": {"block_rows": 8}, "value": 1.0,
                                "meta": {}, "time": 0.0}}, f)
        cache = AutotuneCache(tmp_cache)
        cache.put("rmsnorm", shape_sig({"ROWS": 8, "D": 32}), "float32",
                  "cpu", {"block_rows": 16}, 1.0)
        on_disk = json.load(open(tmp_cache))
        assert future in on_disk  # preserved, not erased
        assert len(on_disk) == 2


class TestResolveBlocksErrorHandling:
    """Regression: resolve_blocks used a bare ``except Exception`` that
    silently masked cache corruption — now it warns once, names the cache
    path, and only catches the expected failure set."""

    def _corrupt_cache(self, path):
        key = AutotuneCache.key("rmsnorm", shape_sig({"ROWS": 8, "D": 32}),
                                "float32", autotune.backend_name())
        with open(path, "w") as f:
            json.dump({key: ["structurally", "corrupt"]}, f)
        return AutotuneCache(path)

    def test_corrupted_cache_warns_once_and_falls_back(self, tmp_cache,
                                                       caplog):
        from repro.autotune import api

        api._warned_cache_paths.clear()
        cache = self._corrupt_cache(tmp_cache)
        defaults = {"block_rows": 256}
        with caplog.at_level(logging.WARNING, logger="repro.autotune"):
            out = autotune.resolve_blocks("rmsnorm", {"ROWS": 8, "D": 32},
                                          "float32", defaults, cache=cache)
        assert out == defaults
        warnings = [r for r in caplog.records
                    if r.levelno == logging.WARNING]
        assert len(warnings) == 1
        assert tmp_cache in warnings[0].getMessage()  # names the path
        # one-time: a second failing lookup does not warn again
        caplog.clear()
        with caplog.at_level(logging.WARNING, logger="repro.autotune"):
            out2 = autotune.resolve_blocks("rmsnorm", {"ROWS": 8, "D": 32},
                                           "float32", defaults, cache=cache)
        assert out2 == defaults
        assert not [r for r in caplog.records
                    if r.levelno == logging.WARNING]

    def test_unexpected_errors_propagate(self, tmp_cache, monkeypatch):
        from repro.autotune import api

        def boom(*a, **kw):
            raise RuntimeError("programming error")

        monkeypatch.setattr(api, "cached_blocks", boom)
        with pytest.raises(RuntimeError, match="programming error"):
            api.resolve_blocks("rmsnorm", {"ROWS": 8, "D": 32}, "float32",
                               {"block_rows": 256})

    def test_caller_errors_propagate(self):
        """Bad call-site arguments (unknown kernel, missing signature
        dims — e.g. a site not migrated to SK) must raise, not silently
        resolve to defaults."""
        with pytest.raises(ValueError, match="missing dims"):
            autotune.resolve_blocks(
                "flash_attention",
                {"B": 1, "S": 256, "H": 4, "KV": 2, "D": 32},  # no SK
                "float32", {"block_q": 128, "block_kv": 128})
        with pytest.raises(ValueError, match="unknown kernel"):
            autotune.resolve_blocks("conv3d", {"B": 1}, "float32", {})


class TestConcurrentWriters:
    """Regression: two processes tuning into ONE cache file must not lose
    entries.  Before the merge-on-save fix, each writer dumped its stale
    in-memory view wholesale — writer B's put erased writer A's entry."""

    @staticmethod
    def _sig(rows):
        return shape_sig({"ROWS": rows, "D": 32})

    def test_interleaved_writers_keep_all_entries(self, tmp_cache):
        a = AutotuneCache(tmp_cache)
        b = AutotuneCache(tmp_cache)
        # both load their (empty) in-memory views before either writes —
        # the lost-update schedule
        assert a.get("rmsnorm", self._sig(8), "float32", "cpu") is None
        assert b.get("rmsnorm", self._sig(16), "float32", "cpu") is None
        a.put("rmsnorm", self._sig(8), "float32", "cpu",
              {"block_rows": 8}, 1.0)
        b.put("rmsnorm", self._sig(16), "float32", "cpu",
              {"block_rows": 16}, 2.0)  # b's view never saw a's entry
        on_disk = json.load(open(tmp_cache))
        assert len(on_disk) == 2
        fresh = AutotuneCache(tmp_cache)
        assert fresh.get_config("rmsnorm", self._sig(8), "float32",
                                "cpu") == {"block_rows": 8}
        assert fresh.get_config("rmsnorm", self._sig(16), "float32",
                                "cpu") == {"block_rows": 16}

    def test_many_interleavings_union(self, tmp_cache):
        """N writers alternating puts: the file ends with all N*K keys."""
        writers = [AutotuneCache(tmp_cache) for _ in range(3)]
        for w in writers:  # load stale (empty) views up front
            assert w.get("rmsnorm", self._sig(1), "float32", "cpu") is None
        for k in range(4):
            for i, w in enumerate(writers):
                w.put("rmsnorm", self._sig(100 * (k + 1) + i), "float32",
                      "cpu", {"block_rows": 8}, float(k))
        assert len(json.load(open(tmp_cache))) == 12

    def test_writers_see_merged_state_after_put(self, tmp_cache):
        """After its own put, a writer's in-memory view includes entries
        merged from disk — no reload needed to resolve them."""
        a = AutotuneCache(tmp_cache)
        b = AutotuneCache(tmp_cache)
        assert b.get("rmsnorm", self._sig(8), "float32", "cpu") is None
        a.put("rmsnorm", self._sig(8), "float32", "cpu",
              {"block_rows": 8}, 1.0)
        b.put("rmsnorm", self._sig(16), "float32", "cpu",
              {"block_rows": 16}, 2.0)
        # b merged a's entry during its save
        assert b.get_config("rmsnorm", self._sig(8), "float32",
                            "cpu") == {"block_rows": 8}

    def test_stale_view_does_not_revert_other_writers_values(self,
                                                             tmp_cache):
        """Value-level lost update: writer A's stale in-memory copy of a
        key another process re-tuned must NOT ride along when A writes an
        unrelated key — only the keys a writer actually modified overlay
        the file."""
        a = AutotuneCache(tmp_cache)
        a.put("rmsnorm", self._sig(8), "float32", "cpu",
              {"block_rows": 8}, 1.0)  # a's view now holds the old entry
        b = AutotuneCache(tmp_cache)
        b.put("rmsnorm", self._sig(8), "float32", "cpu",
              {"block_rows": 32}, 2.0)  # b re-tunes the SAME key
        a.put("rmsnorm", self._sig(16), "float32", "cpu",
              {"block_rows": 16}, 1.0)  # a writes something unrelated
        fresh = AutotuneCache(tmp_cache)
        assert fresh.get_config("rmsnorm", self._sig(8), "float32",
                                "cpu") == {"block_rows": 32}  # b's survives

    def test_merge_still_drops_stale_schemas(self, tmp_cache):
        """Merge-on-save must not resurrect older-schema entries."""
        stale_key = "flash_attention|B1|float32|cpu"  # v1 (unversioned)
        with open(tmp_cache, "w") as f:
            json.dump({stale_key: {"config": {}, "value": 1.0,
                                   "meta": {}, "time": 0.0}}, f)
        cache = AutotuneCache(tmp_cache)
        cache.put("rmsnorm", self._sig(8), "float32", "cpu",
                  {"block_rows": 8}, 1.0)
        on_disk = json.load(open(tmp_cache))
        assert stale_key not in on_disk
        assert len(on_disk) == 1


class TestTrainConfigCache:
    """The live joint mode's train-step entry: persists + reloads alongside
    kernel and serve-config entries in the same cache file."""

    def test_put_and_reload(self, tmp_cache):
        sig_dims = {"S": 32, "B": 8, "H": 4, "KV": 4, "D": 16}
        knobs = {"microbatches": 2, "remat": "none", "attn_block_q": 0,
                 "attn_block_kv": 0, "compression": "none"}
        autotune.put_train_config(sig_dims, "float32", knobs, 1234.5)
        assert autotune.cached_train_config(sig_dims, "float32") == knobs
        # keyed by workload shape: a different microbatch seq misses
        assert autotune.cached_train_config(dict(sig_dims, S=64),
                                            "float32") is None
        fresh = AutotuneCache(os.environ["REPRO_AUTOTUNE_CACHE"])
        assert autotune.cached_train_config(sig_dims, "float32",
                                            cache=fresh) == knobs

    def test_three_system_entries_coexist(self, tmp_cache):
        """Kernel + serve_engine + train_step winners in ONE file — what
        --joint --real persists."""
        autotune.default_cache().put(
            "decode_attention", shape_sig({"B": 8, "S": 128, "H": 4,
                                           "KV": 4, "D": 16}),
            "float32", "cpu", {"block_kv": 128}, 100.0)
        autotune.put_serve_config({"S": 128, "H": 4, "KV": 4, "D": 16},
                                  "float32", {"max_batch": 8}, 100.0)
        autotune.put_train_config({"S": 32, "B": 8, "H": 4, "KV": 4,
                                   "D": 16}, "float32",
                                  {"microbatches": 2}, 100.0)
        on_disk = json.load(open(os.environ["REPRO_AUTOTUNE_CACHE"]))
        systems = {k.split("|")[1] for k in on_disk}
        assert systems == {"decode_attention", autotune.SERVE_SYSTEM,
                           autotune.TRAIN_SYSTEM}


class TestServeConfigCache:
    """The joint mode's serve-config entry: persists + reloads alongside
    kernel entries in the same cache file."""

    def test_put_and_reload(self, tmp_cache):
        sig_dims = {"S": 2048, "H": 16, "KV": 4, "D": 64}
        knobs = {"max_batch": 32, "prefill_chunk": 512,
                 "kv_cache_pages": 4096, "schedule": "fifo"}
        autotune.put_serve_config(sig_dims, "float32", knobs, 3900.0)
        assert autotune.cached_serve_config(sig_dims, "float32") == knobs
        # keyed by shape: a different serving window misses
        other = dict(sig_dims, S=4096)
        assert autotune.cached_serve_config(other, "float32") is None
        # fresh cache object re-reads from disk
        fresh = AutotuneCache(tmp_cache)
        assert autotune.cached_serve_config(sig_dims, "float32",
                                            cache=fresh) == knobs

    def test_pre_sharing_cache_entry_still_deploys(self, tmp_cache):
        """Regression: winners persisted before the share_prefix/draft_len
        knobs existed must deploy with both features off (the page_policy
        precedent) — widening the knob space must not invalidate caches
        written by older builds."""
        from repro.serve.space import apply_serve_knobs

        sig_dims = {"S": 256, "H": 4, "KV": 4, "D": 16}
        old_shape = {"max_batch": 8, "prefill_chunk": 128,
                     "kv_cache_pages": 512, "schedule": "sjf"}
        autotune.put_serve_config(sig_dims, "float32", old_shape, 1234.0)
        loaded = autotune.cached_serve_config(sig_dims, "float32")
        assert "share_prefix" not in loaded and "draft_len" not in loaded
        cfg = apply_serve_knobs(loaded)
        assert cfg.schedule == "sjf"
        assert cfg.page_policy == "reserve"  # the PR-5 back-compat rule
        assert cfg.share_prefix is False and cfg.draft_len == 0
        # and a widened-space winner round-trips the new knobs
        new_shape = dict(old_shape, share_prefix=1, draft_len=4,
                         page_policy="on_demand")
        autotune.put_serve_config(sig_dims, "float32", new_shape, 2000.0)
        cfg2 = apply_serve_knobs(autotune.cached_serve_config(
            sig_dims, "float32"))
        assert cfg2.share_prefix is True and cfg2.draft_len == 4
        assert cfg2.page_policy == "on_demand"


class TestWorkloadKeyedEntries:
    """(PR 8) v3 keys carry a trailing workload-signature component, so
    serve winners tuned under different live request mixes coexist at one
    model shape — the online retuner's transfer set."""

    SIG_DIMS = {"S": 48, "H": 4, "KV": 2, "D": 16}
    WS = "a0.50_d12_g8_p24_r0.35_s0.30_x0.60"

    def test_workload_and_generic_entries_coexist(self, tmp_cache):
        autotune.put_serve_config(self.SIG_DIMS, "float32",
                                  {"max_batch": 4}, 100.0)
        autotune.put_serve_config(self.SIG_DIMS, "float32",
                                  {"max_batch": 8}, 200.0,
                                  workload=self.WS)
        generic = autotune.cached_serve_config(self.SIG_DIMS, "float32")
        at_ws = autotune.cached_serve_config(self.SIG_DIMS, "float32",
                                             workload=self.WS)
        assert generic == {"max_batch": 4}
        assert at_ws == {"max_batch": 8}
        # an unknown signature is an exact-key miss (transfer is the
        # caller's job, via serve_config_candidates)
        assert autotune.cached_serve_config(
            self.SIG_DIMS, "float32", workload="a9.99_d1_g1_p1_r0_s0_x0"
        ) is None

    def test_candidates_scan_by_signature(self, tmp_cache):
        autotune.put_serve_config(self.SIG_DIMS, "float32",
                                  {"max_batch": 4}, 100.0)
        autotune.put_serve_config(self.SIG_DIMS, "float32",
                                  {"max_batch": 8}, 200.0,
                                  workload=self.WS)
        # a different shape must not leak into the candidate set
        autotune.put_serve_config(dict(self.SIG_DIMS, S=96), "float32",
                                  {"max_batch": 2}, 50.0, workload=self.WS)
        cands = autotune.serve_config_candidates(self.SIG_DIMS, "float32")
        assert set(cands) == {"-", self.WS}
        assert cands[self.WS]["config"] == {"max_batch": 8}
        assert cands["-"]["config"] == {"max_batch": 4}

    def test_workload_component_is_sanitized(self, tmp_cache):
        """A ``|`` inside a workload string must not corrupt the key
        layout (it is the key separator)."""
        cache = autotune.default_cache()
        cache.put("k", "s", "float32", "cpu", {"a": 1}, 1.0,
                  workload="bad|sig")
        assert cache.get("k", "s", "float32", "cpu",
                         workload="bad|sig")["config"] == {"a": 1}
        on_disk = json.load(open(os.environ["REPRO_AUTOTUNE_CACHE"]))
        assert all(len(k.split("|")) == 7 for k in on_disk)
        assert set(cache.scan_workloads("k", "s", "float32", "cpu")) == \
            {"bad/sig"}


class TestCacheKeyCanonicalization:
    """(PR 8 satellite) Every producer must serialize the identical key
    from equivalent inputs: numpy integer dims, python ints, and the
    three entry kinds (kernel / serve / train) all round-trip through one
    canonical form — a formatting mismatch is a silent cache miss."""

    def test_numpy_dims_key_like_python_ints(self, tmp_cache):
        np_dims = {"S": np.int64(48), "H": np.int32(4),
                   "KV": np.int64(2), "D": np.int32(16)}
        py_dims = {"S": 48, "H": 4, "KV": 2, "D": 16}
        autotune.put_serve_config(np_dims, "float32", {"max_batch": 4},
                                  1.0)
        assert autotune.cached_serve_config(py_dims, "float32") == \
            {"max_batch": 4}
        autotune.put_train_config(dict(py_dims, B=np.int64(8)), "float32",
                                  {"microbatches": 2}, 1.0)
        assert autotune.cached_train_config(dict(py_dims, B=8),
                                            "float32") == \
            {"microbatches": 2}

    def test_all_three_entry_kinds_round_trip(self, tmp_cache):
        """One file, three producers, one schema: every entry written
        through its public producer reloads from a FRESH cache object
        (true disk round-trip) under the current schema version."""
        kernel_sig = shape_sig({"ROWS": 8, "D": 32})
        autotune.default_cache().put("rmsnorm", kernel_sig, "float32",
                                     "cpu", {"block_rows": 8}, 10.0)
        autotune.put_serve_config({"S": 48, "H": 4, "KV": 2, "D": 16},
                                  "float32", {"max_batch": 4}, 20.0,
                                  workload="a0.50_d1_g1_p1_r0.00_s0.00_x?")
        autotune.put_train_config({"S": 32, "B": 8, "H": 4, "KV": 4,
                                   "D": 16}, "float32",
                                  {"microbatches": 2}, 30.0)
        fresh = AutotuneCache(os.environ["REPRO_AUTOTUNE_CACHE"])
        assert fresh.get_config("rmsnorm", kernel_sig, "float32",
                                "cpu") == {"block_rows": 8}
        assert autotune.cached_serve_config(
            {"S": 48, "H": 4, "KV": 2, "D": 16}, "float32",
            workload="a0.50_d1_g1_p1_r0.00_s0.00_x?",
            cache=fresh) == {"max_batch": 4}
        assert autotune.cached_train_config(
            {"S": 32, "B": 8, "H": 4, "KV": 4, "D": 16}, "float32",
            cache=fresh) == {"microbatches": 2}
        on_disk = json.load(open(os.environ["REPRO_AUTOTUNE_CACHE"]))
        assert len(on_disk) == 3
        for k in on_disk:
            parts = k.split("|")
            assert parts[0] == f"v{autotune.SCHEMA_VERSION}"
            assert len(parts) == 7  # workload + mesh on EVERY key

    def test_key_is_pure_string_function(self):
        assert AutotuneCache.key("k", "s", "float32", "cpu") == \
            AutotuneCache.key("k", "s", "float32", "cpu", workload="")
        assert AutotuneCache.key("k", "s", "float32", "cpu").endswith("|-|1dev")


class TestSchemaV2Migration:
    """(PR 8) The v3 bump MIGRATES v2 entries (same meaning, generic
    workload signature) instead of dropping them — a pre-PR8 tuned cache
    keeps its winners."""

    V2_KEY = "v2|rmsnorm|D32_ROWS8|float32|cpu"

    def _seed_v2(self, path):
        with open(path, "w") as f:
            json.dump({self.V2_KEY: {
                "config": {"block_rows": 8}, "value": 42.0,
                "meta": {"mode": "est"}, "time": 0.0}}, f)

    def test_v2_entry_resolves_at_generic_workload(self, tmp_cache):
        self._seed_v2(tmp_cache)
        cache = AutotuneCache(tmp_cache)
        got = cache.get("rmsnorm", "D32_ROWS8", "float32", "cpu")
        assert got and got["config"] == {"block_rows": 8}
        assert got["value"] == 42.0

    def test_migration_becomes_physical_on_write(self, tmp_cache):
        self._seed_v2(tmp_cache)
        cache = AutotuneCache(tmp_cache)
        cache.put("other", "sig", "float32", "cpu", {"a": 1}, 1.0)
        on_disk = json.load(open(tmp_cache))
        assert self.V2_KEY not in on_disk
        migrated = f"v{autotune.SCHEMA_VERSION}|rmsnorm|D32_ROWS8" \
                   "|float32|cpu|-|1dev"
        assert on_disk[migrated]["config"] == {"block_rows": 8}

    def test_native_v3_wins_over_migrated_v2(self, tmp_cache):
        """A re-tuned (native current-schema) entry must never be
        shadowed by its pre-migration ancestor sharing the file."""
        native = AutotuneCache.key("rmsnorm", "D32_ROWS8", "float32",
                                   "cpu")
        with open(tmp_cache, "w") as f:
            json.dump({
                self.V2_KEY: {"config": {"block_rows": 8}, "value": 42.0,
                              "meta": {}, "time": 0.0},
                native: {"config": {"block_rows": 16}, "value": 99.0,
                         "meta": {}, "time": 1.0},
            }, f)
        cache = AutotuneCache(tmp_cache)
        assert cache.get_config("rmsnorm", "D32_ROWS8", "float32",
                                "cpu") == {"block_rows": 16}

    def test_pre_v2_still_drops(self, tmp_cache):
        with open(tmp_cache, "w") as f:
            json.dump({"rmsnorm|D32_ROWS8|float32|cpu": {
                "config": {"block_rows": 8}, "value": 1.0, "meta": {},
                "time": 0.0}}, f)
        cache = AutotuneCache(tmp_cache)
        assert cache.get("rmsnorm", "D32_ROWS8", "float32", "cpu") is None
