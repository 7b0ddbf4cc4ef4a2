"""Per-kernel validation: shape/dtype sweeps + hypothesis, allclose against
the pure-jnp oracles in ``repro.kernels.ref`` (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gla import gla_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ref import attention_ref, gla_ref, rmsnorm_ref

TOL = {
    jnp.float32: dict(rtol=2e-5, atol=2e-5),
    jnp.bfloat16: dict(rtol=2e-2, atol=2e-2),
}


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,S,H,KV,D,bq,bk",
        [
            (1, 16, 1, 1, 8, 16, 16),     # minimal
            (2, 64, 4, 2, 16, 32, 32),    # GQA
            (1, 96, 8, 1, 32, 32, 32),    # MQA, non-square blocks
            (2, 100, 4, 4, 16, 32, 16),   # ragged seq vs blocks (padding)
            (1, 128, 2, 2, 64, 64, 128),  # bq < bk
        ],
    )
    def test_against_ref(self, dtype, B, S, H, KV, D, bq, bk):
        rng = np.random.default_rng(hash((B, S, H, KV, D)) % 2**31)
        q = _rand(rng, (B, S, H, D), dtype)
        k = _rand(rng, (B, S, KV, D), dtype)
        v = _rand(rng, (B, S, KV, D), dtype)
        out = flash_attention_pallas(q, k, v, causal=True, block_q=bq,
                                     block_kv=bk, interpret=True)
        ref = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **TOL[dtype])

    @pytest.mark.parametrize("window", [8, 24, 64])
    def test_sliding_window(self, window):
        rng = np.random.default_rng(window)
        q = _rand(rng, (2, 72, 4, 16), jnp.float32)
        k = _rand(rng, (2, 72, 2, 16), jnp.float32)
        v = _rand(rng, (2, 72, 2, 16), jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=True, window=window,
                                     block_q=16, block_kv=16, interpret=True)
        ref = attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_non_causal(self):
        rng = np.random.default_rng(7)
        q = _rand(rng, (1, 48, 2, 16), jnp.float32)
        k = _rand(rng, (1, 48, 2, 16), jnp.float32)
        v = _rand(rng, (1, 48, 2, 16), jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=False, block_q=16,
                                     block_kv=16, interpret=True)
        ref = attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @given(
        S=st.integers(4, 80),
        H=st.sampled_from([1, 2, 4]),
        G=st.sampled_from([1, 2]),
        D=st.sampled_from([8, 16]),
        bq=st.sampled_from([8, 16, 32]),
        bk=st.sampled_from([8, 16, 32]),
        seed=st.integers(0, 100),
    )
    @settings(max_examples=25, deadline=None)
    def test_property_random_shapes(self, S, H, G, D, bq, bk, seed):
        KV = max(1, H // G)
        H = KV * G
        rng = np.random.default_rng(seed)
        q = _rand(rng, (1, S, H, D), jnp.float32)
        k = _rand(rng, (1, S, KV, D), jnp.float32)
        v = _rand(rng, (1, S, KV, D), jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=True, block_q=bq,
                                     block_kv=bk, interpret=True)
        ref = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


class TestRMSNorm:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape,block", [
        ((4, 32), 4), ((3, 7, 64), 16), ((1, 128), 256), ((5, 100), 32),
    ])
    def test_against_ref(self, dtype, shape, block):
        rng = np.random.default_rng(hash(shape) % 2**31)
        x = _rand(rng, shape, dtype)
        s = _rand(rng, (shape[-1],), jnp.float32)
        out = rmsnorm_pallas(x, s, block_rows=block, interpret=True)
        ref = rmsnorm_ref(x, s)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **TOL[dtype])

    @given(rows=st.integers(1, 50), d=st.sampled_from([8, 32, 128]),
           seed=st.integers(0, 50))
    @settings(max_examples=20, deadline=None)
    def test_property(self, rows, d, seed):
        rng = np.random.default_rng(seed)
        x = _rand(rng, (rows, d), jnp.float32)
        s = _rand(rng, (d,), jnp.float32)
        out = rmsnorm_pallas(x, s, block_rows=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(rmsnorm_ref(x, s)),
                                   rtol=2e-5, atol=2e-5)


class TestGLA:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,S,H,dk,dv,chunk", [
        (1, 16, 1, 4, 4, 8),
        (2, 64, 3, 8, 16, 16),
        (1, 70, 2, 16, 8, 32),   # ragged
        (2, 128, 4, 32, 32, 64),
    ])
    def test_against_ref(self, dtype, B, S, H, dk, dv, chunk):
        rng = np.random.default_rng(hash((B, S, H, dk, dv)) % 2**31)
        q = _rand(rng, (B, S, H, dk), dtype)
        k = _rand(rng, (B, S, H, dk), dtype)
        v = _rand(rng, (B, S, H, dv), dtype)
        g = jnp.asarray(-np.abs(rng.normal(size=(B, S, H)) * 0.3), jnp.float32)
        y, state = gla_pallas(q, k, v, g, chunk=chunk, interpret=True)
        yr, sr = gla_ref(q, k, v, g)
        tol = TOL[dtype]
        # chunked vs O(S^2) reference accumulate in different orders; the
        # largest case needs the same slack the state comparison gets
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(yr, np.float32),
                                   rtol=max(tol["rtol"], 5e-5),
                                   atol=max(tol["atol"], 5e-5))
        np.testing.assert_allclose(np.asarray(state), np.asarray(sr),
                                   rtol=max(tol["rtol"], 1e-4),
                                   atol=max(tol["atol"], 1e-4))

    def test_matches_model_core(self):
        """Kernel ≡ the chunked-jnp core the models actually run."""
        from repro.models.gla import chunked_gla

        rng = np.random.default_rng(0)
        q = _rand(rng, (2, 48, 2, 8), jnp.float32)
        k = _rand(rng, (2, 48, 2, 8), jnp.float32)
        v = _rand(rng, (2, 48, 2, 8), jnp.float32)
        g = jnp.asarray(-np.abs(rng.normal(size=(2, 48, 2)) * 0.2), jnp.float32)
        y1, s1 = gla_pallas(q, k, v, g, chunk=16, interpret=True)
        y2, s2 = chunked_gla(q, k, v, g, chunk=16)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=2e-5, atol=2e-5)

    @given(S=st.integers(4, 60), chunk=st.sampled_from([4, 8, 16, 32]),
           seed=st.integers(0, 50))
    @settings(max_examples=20, deadline=None)
    def test_property_chunk_invariance(self, S, chunk, seed):
        """Output must not depend on the chunk size (tiling invariance)."""
        rng = np.random.default_rng(seed)
        q = _rand(rng, (1, S, 1, 8), jnp.float32)
        k = _rand(rng, (1, S, 1, 8), jnp.float32)
        v = _rand(rng, (1, S, 1, 8), jnp.float32)
        g = jnp.asarray(-np.abs(rng.normal(size=(1, S, 1)) * 0.5), jnp.float32)
        y, st_ = gla_pallas(q, k, v, g, chunk=chunk, interpret=True)
        yr, sr = gla_ref(q, k, v, g)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=5e-5, atol=5e-5)
        np.testing.assert_allclose(np.asarray(st_), np.asarray(sr),
                                   rtol=1e-4, atol=1e-4)


class TestOpsWrappers:
    def test_ops_jit(self):
        from repro.kernels import ops

        rng = np.random.default_rng(0)
        q = _rand(rng, (1, 32, 2, 16), jnp.float32)
        k = _rand(rng, (1, 32, 2, 16), jnp.float32)
        v = _rand(rng, (1, 32, 2, 16), jnp.float32)
        out = ops.flash_attention(q, k, v, block_q=16, block_kv=16)
        ref = attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        x = _rand(rng, (8, 32), jnp.float32)
        s = _rand(rng, (32,), jnp.float32)
        np.testing.assert_allclose(np.asarray(ops.rmsnorm(x, s)),
                                   np.asarray(rmsnorm_ref(x, s)),
                                   rtol=2e-5, atol=2e-5)


class TestPagedDecode:
    """Paged decode attention: the Pallas kernel gathers K/V through the
    page table (scalar-prefetch index maps) and must match both its jnp
    gather reference and the dense attention oracle on the logically
    ordered cache."""

    def _pool(self, rng, B, maxg, T, KV, D, dtype, extra=3):
        G = B * maxg + extra  # a few unused groups (incl. scratch-like 0)
        kp = _rand(rng, (G, KV, T, D), dtype)
        vp = _rand(rng, (G, KV, T, D), dtype)
        # random non-identity table over groups 1..G-1, unique per entry
        perm = 1 + rng.permutation(G - 1)[:B * maxg]
        pt = jnp.asarray(perm.reshape(B, maxg), jnp.int32)
        return kp, vp, pt

    @staticmethod
    def _blocks_of(monkeypatch, n, KV, T, D, dtype):
        """Shrink the kernel's block to ``n`` groups, so that tiny shapes
        still take several grid steps a slot."""
        from repro.serve import paging

        monkeypatch.setattr(paging, "BLOCK_BYTES",
                            n * KV * T * D * jnp.dtype(dtype).itemsize)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,H,KV,D,T,maxg", [
        (1, 2, 2, 8, 16, 2),
        (2, 8, 2, 16, 32, 3),   # GQA, multi-page
        (3, 4, 1, 32, 16, 4),   # MQA
        # six slots at the edge lengths 0, 1, T, nT, nT+1, MAXG*T, with
        # KV in {1, 2, 8} and Gq in {1, 5}; MAXG is no multiple of n = 3
        (6, 1, 1, 8, 64, 5),
        (6, 5, 1, 8, 16, 7),
        (6, 2, 2, 16, 16, 8),
        (6, 10, 2, 16, 64, 5),
        (6, 8, 8, 8, 16, 7),
        (6, 40, 8, 8, 64, 4),
    ])
    def test_against_refs(self, monkeypatch, dtype, B, H, KV, D, T, maxg):
        from repro.kernels.paged_attention import (gather_pages,
                                                   paged_attention_ref,
                                                   paged_flash_decode_pallas)

        n = 3  # groups a grid step
        self._blocks_of(monkeypatch, n, KV, T, D, dtype)
        rng = np.random.default_rng(hash((B, H, KV, D, T)) % 2**31)
        q = _rand(rng, (B, H, D), dtype)
        kp, vp, pt = self._pool(rng, B, maxg, T, KV, D, dtype)
        if B == 6:
            lengths = np.minimum([0, 1, T, n * T, n * T + 1, maxg * T],
                                 maxg * T)
        else:
            lengths = rng.integers(1, maxg * T, size=B)
        # as in the engine, groups past a slot's length map to scratch 0
        pt = np.array(pt)
        for b in range(B):
            pt[b, -(-int(lengths[b]) // T):] = 0
        pt = jnp.asarray(pt)
        lengths = jnp.asarray(lengths, jnp.int32)
        out = paged_flash_decode_pallas(q, kp, vp, pt, lengths,
                                        interpret=True)
        ref = paged_attention_ref(q, kp, vp, pt, lengths)
        live = np.asarray(lengths) > 0
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[live],
            np.asarray(ref, np.float32)[live], **TOL[dtype])
        # an idle slot (length 0) reads zeros, not NaN
        np.testing.assert_array_equal(
            np.asarray(out, np.float32)[~live], 0.0)
        # gather-to-dense oracle per sequence
        kd = gather_pages(kp, pt)
        vd = gather_pages(vp, pt)
        for b in np.flatnonzero(live):
            L = int(lengths[b])
            dense = attention_ref(q[b:b + 1, None], kd[b:b + 1, :L],
                                  vd[b:b + 1, :L], causal=False)[:, 0]
            np.testing.assert_allclose(
                np.asarray(out[b:b + 1], np.float32),
                np.asarray(dense, np.float32), **TOL[dtype])

    @pytest.mark.parametrize("KV,T,D,itemsize,maxg,n", [
        (8, 64, 128, 2, 64, 8),    # Qwen2.5-32B on one chip: 512 tokens
        (2, 64, 128, 2, 64, 32),   # its TP (1, 4) shard
        (8, 512, 128, 2, 8, 1),    # pages_per_block 32: one group a step
        (2, 16, 8, 4, 5, 5),       # tiny: the whole row in one step
    ])
    def test_block_groups(self, KV, T, D, itemsize, maxg, n):
        from repro.serve.paging import block_groups

        assert block_groups(KV, T, D, itemsize, maxg) == n

    @pytest.mark.parametrize("KV,Gq,T", [(2, 5, 16), (8, 1, 64)])
    def test_groups_per_step_leave_the_bits(self, monkeypatch, KV, Gq, T):
        """The softmax advances one group at a time whatever the block,
        so 1, 2 or 3 groups a grid step give the same bits."""
        from repro.kernels.paged_attention import paged_flash_decode_pallas

        rng = np.random.default_rng(5)
        B, D, maxg = 4, 16, 5
        q = _rand(rng, (B, KV * Gq, D), jnp.float32)
        kp, vp, pt = self._pool(rng, B, maxg, T, KV, D, jnp.float32)
        lengths = jnp.asarray([1, 2 * T + 3, 4 * T, maxg * T], jnp.int32)
        outs = []
        for n in (1, 2, 3):
            self._blocks_of(monkeypatch, n, KV, T, D, jnp.float32)
            outs.append(np.asarray(paged_flash_decode_pallas(
                q, kp, vp, pt, lengths, interpret=True)))
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(outs[0], outs[2])

    def test_page_table_permutation_invariance(self, monkeypatch):
        """Physically scattering the same logical cache across different
        groups must not change the output at all."""
        from repro.kernels.paged_attention import paged_flash_decode_pallas

        rng = np.random.default_rng(11)
        B, H, KV, D, T, maxg = 2, 4, 2, 16, 16, 3
        self._blocks_of(monkeypatch, 2, KV, T, D, jnp.float32)
        q = _rand(rng, (B, H, D), jnp.float32)
        logical_k = _rand(rng, (B, maxg * T, KV, D), jnp.float32)
        logical_v = _rand(rng, (B, maxg * T, KV, D), jnp.float32)
        lengths = jnp.asarray([40, 17], jnp.int32)
        outs = []
        for seed in (0, 1):
            prm = 1 + np.random.default_rng(seed).permutation(B * maxg)
            G = B * maxg + 2
            kp = np.zeros((G, KV, T, D), np.float32)
            vp = np.zeros((G, KV, T, D), np.float32)
            pt = prm.reshape(B, maxg)
            for b in range(B):
                for g in range(maxg):
                    sl = slice(g * T, (g + 1) * T)
                    kp[pt[b, g]] = np.asarray(logical_k)[b, sl].swapaxes(0, 1)
                    vp[pt[b, g]] = np.asarray(logical_v)[b, sl].swapaxes(0, 1)
            outs.append(np.asarray(paged_flash_decode_pallas(
                q, jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(pt, jnp.int32), lengths, interpret=True)))
        np.testing.assert_array_equal(outs[0], outs[1])

    @given(maxg=st.integers(1, 4), T=st.sampled_from([16, 32]),
           kv_len=st.integers(1, 120), seed=st.integers(0, 50))
    @settings(max_examples=15, deadline=None)
    def test_property_dynamic_length(self, maxg, T, kv_len, seed):
        from repro.kernels.paged_attention import (paged_attention_ref,
                                                   paged_flash_decode_pallas)

        kv_len = min(kv_len, maxg * T)
        rng = np.random.default_rng(seed)
        q = _rand(rng, (1, 4, 8), jnp.float32)
        kp, vp, pt = self._pool(rng, 1, maxg, T, 2, 8, jnp.float32)
        lengths = jnp.asarray([kv_len], jnp.int32)
        out = paged_flash_decode_pallas(q, kp, vp, pt, lengths,
                                        interpret=True)
        ref = paged_attention_ref(q, kp, vp, pt, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)

    def test_ops_wrapper_resolves_launch_knobs(self):
        from repro.kernels import ops

        rng = np.random.default_rng(3)
        q = _rand(rng, (2, 4, 16), jnp.float32)
        kp, vp, pt = self._pool(rng, 2, 2, 16, 2, 16, jnp.float32)
        lengths = jnp.asarray([20, 7], jnp.int32)
        out = ops.paged_flash_decode(q, kp, vp, pt, lengths)
        from repro.kernels.paged_attention import paged_attention_ref

        np.testing.assert_allclose(
            np.asarray(out), np.asarray(paged_attention_ref(
                q, kp, vp, pt, lengths)), rtol=2e-5, atol=2e-5)


class TestFlashDecode:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,S,H,KV,D,bkv", [
        (1, 32, 2, 2, 8, 16),
        (2, 96, 8, 2, 16, 32),
        (1, 100, 4, 1, 32, 32),   # MQA + ragged cache
    ])
    def test_against_ref(self, dtype, B, S, H, KV, D, bkv):
        from repro.kernels.decode_attention import flash_decode_pallas

        rng = np.random.default_rng(hash((B, S, H, KV, D)) % 2**31)
        q = _rand(rng, (B, H, D), dtype)
        k = _rand(rng, (B, S, KV, D), dtype)
        v = _rand(rng, (B, S, KV, D), dtype)
        for kv_len in (1, S // 3, S):
            out = flash_decode_pallas(q, k, v, kv_len, block_kv=bkv,
                                      interpret=True)
            ref = attention_ref(q[:, None], k[:, :kv_len], v[:, :kv_len],
                                causal=False)[:, 0]
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(ref, np.float32),
                **TOL[dtype])

    @given(S=st.integers(8, 80), kv_len=st.integers(1, 80),
           bkv=st.sampled_from([8, 16, 32]), seed=st.integers(0, 50))
    @settings(max_examples=15, deadline=None)
    def test_property_dynamic_length(self, S, kv_len, bkv, seed):
        from repro.kernels.decode_attention import flash_decode_pallas

        kv_len = min(kv_len, S)
        rng = np.random.default_rng(seed)
        q = _rand(rng, (1, 4, 8), jnp.float32)
        k = _rand(rng, (1, S, 2, 8), jnp.float32)
        v = _rand(rng, (1, S, 2, 8), jnp.float32)
        out = flash_decode_pallas(q, k, v, kv_len, block_kv=bkv,
                                  interpret=True)
        ref = attention_ref(q[:, None], k[:, :kv_len], v[:, :kv_len],
                            causal=False)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)
