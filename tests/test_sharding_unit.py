"""Unit tests for the logical-axis sharding layer + HLO cost analyzer."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (
    DEFAULT_RULES,
    DP_ALL_RULES,
    RULE_PRESETS,
    AxisRules,
    axis_rules,
    constrain,
    spec_for_shape,
)


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    """Duck-typed mesh: only .shape is consulted by spec_for_shape."""

    def __init__(self, **axes):
        self.shape = dict(axes)


class TestSpecForShape:
    def test_basic_mapping(self):
        mesh = FakeMesh(data=16, model=16)
        spec = spec_for_shape((256, 4096), ("batch", "seq"), DEFAULT_RULES,
                              mesh)
        assert spec == P(("data",))  # pod dropped (absent), seq unsharded

    def test_divisibility_fallback(self):
        mesh = FakeMesh(data=16, model=16)
        # 40 heads % 16 != 0 -> heads mapping dropped
        spec = spec_for_shape((5120, 40, 128), ("embed_fsdp", "heads",
                                                "head_dim"),
                              DEFAULT_RULES, mesh)
        assert spec == P("data")

    def test_axis_used_once(self):
        mesh = FakeMesh(data=16, model=16)
        spec = spec_for_shape((64, 64), ("ff", "vocab"), DEFAULT_RULES, mesh)
        # both want "model"; first dim wins
        assert spec == P("model")

    def test_multi_axis_batch(self):
        mesh = FakeMesh(pod=2, data=16, model=16)
        spec = spec_for_shape((512, 10), ("batch", None), DP_ALL_RULES, mesh)
        assert spec == P(("pod", "data", "model"))

    def test_missing_mesh_axis_dropped(self):
        mesh = FakeMesh(data=4)
        spec = spec_for_shape((8, 8), ("batch", "ff"), DEFAULT_RULES, mesh)
        assert spec == P("data")  # pod and model axes absent

    def test_rules_replace(self):
        r = DEFAULT_RULES.replace(seq="model", brand_new="data")
        assert r.lookup("seq") == "model"
        assert r.lookup("brand_new") == "data"
        assert DEFAULT_RULES.lookup("seq") is None  # immutable

    def test_presets_exist(self):
        for name in ("dp", "dp_all", "fsdp_all", "tp", "fsdp_tp"):
            assert name in RULE_PRESETS

    def test_constrain_noop_without_rules(self):
        x = jnp.ones((4, 4))
        y = constrain(x, "batch", "embed")
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestServePresets:
    """Property tests for the serve_tp / serve_replicas presets the
    sharded engine activates (PR 9): what each layout actually pins."""

    SHAPES = [
        ((8, 64, 16), ("batch", "heads", "head_dim")),
        ((8, 128, 4, 16), ("batch", "seq", "kv_heads", "head_dim")),
        ((64, 256), ("embed", "ff")),
        ((64, 512), ("embed", "vocab")),
    ]

    def test_presets_registered(self):
        assert "serve_tp" in RULE_PRESETS
        assert "serve_replicas" in RULE_PRESETS

    def test_tp_splits_model_axes_only(self):
        mesh = FakeMesh(data=1, model=4)
        rules = RULE_PRESETS["serve_tp"]
        for shape, axes in self.SHAPES:
            spec = spec_for_shape(shape, axes, rules, mesh)
            # size-1 data axis drops: batch never shards on pure TP
            assert "data" not in [s for e in spec for s in
                                  ([e] if isinstance(e, str) else e or [])]
        assert spec_for_shape((8, 64, 16),
                              ("batch", "heads", "head_dim"),
                              rules, mesh) == P(None, "model")
        assert spec_for_shape((64, 256), ("embed", "ff"),
                              rules, mesh) == P(None, "model")

    def test_replicas_shard_batch_only(self):
        mesh = FakeMesh(data=4, model=1)
        rules = RULE_PRESETS["serve_replicas"]
        for shape, axes in self.SHAPES:
            spec = spec_for_shape(shape, axes, rules, mesh)
            flat = [s for e in spec for s in
                    ([e] if isinstance(e, str) else e or [])]
            assert "model" not in flat
            assert ("data" in flat) == ("batch" in axes)

    def test_tp_degenerates_to_replicas_on_data_mesh(self):
        """serve_tp on a (K, 1) mesh IS serve_replicas: the size-1 model
        axis drops from every rule, leaving only batch -> data.  This is
        why the engine can default to serve_tp for both layouts."""
        mesh = FakeMesh(data=4, model=1)
        for shape, axes in self.SHAPES:
            assert spec_for_shape(shape, axes,
                                  RULE_PRESETS["serve_tp"], mesh) \
                == spec_for_shape(shape, axes,
                                  RULE_PRESETS["serve_replicas"], mesh)

    def test_non_dividing_dim_replicates(self):
        mesh = FakeMesh(data=1, model=8)
        # 12 heads % 8 != 0 -> the dim replicates rather than erroring
        spec = spec_for_shape((4, 12, 16), ("batch", "heads", "head_dim"),
                              RULE_PRESETS["serve_tp"], mesh)
        assert spec == P()

    def test_pool_axes_shard_kv_heads_only(self):
        """The paged pool's declared layout: page-group axis whole (the
        scalar-prefetched page table indexes it), kv_heads split."""
        from repro.kernels.paged_attention import POOL_AXES
        mesh = FakeMesh(data=1, model=2)
        spec = spec_for_shape((8, 4, 64, 16), POOL_AXES,
                              RULE_PRESETS["serve_tp"], mesh)
        assert spec == P(None, "model")


class TestHloCostAnalyzer:
    def test_scan_trip_count(self):
        from repro.utils.hlo_cost import analyze_hlo

        def body(x, w):
            return x @ w, ()

        def f(x, ws):
            return jax.lax.scan(body, x, ws)[0]

        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
        c = jax.jit(f).lower(x, ws).compile()
        a = analyze_hlo(c.as_text())
        assert a.flops == pytest.approx(7 * 2 * 64**3, rel=0.01)
        assert not a.unresolved_trips

    def test_nested_scan(self):
        from repro.utils.hlo_cost import analyze_hlo

        def f(x, ws):
            def outer(xx, w):
                def inner(y, _):
                    return y @ w, ()
                return jax.lax.scan(inner, xx, None, length=5)[0], ()
            return jax.lax.scan(outer, x, ws)[0]

        x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
        ws = jax.ShapeDtypeStruct((3, 32, 32), jnp.float32)
        c = jax.jit(f).lower(x, ws).compile()
        a = analyze_hlo(c.as_text())
        assert a.flops == pytest.approx(15 * 2 * 32**3, rel=0.01)

    def test_collectives_counted_with_trips(self):
        """psum inside a scan must be multiplied by the trip count —
        runs in a subprocess with 8 host devices."""
        script = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            import sys
            sys.path.insert(0, "src")
            from repro.utils.hlo_cost import analyze_hlo

            mesh = jax.make_mesh((8,), ("d",))
            def body(c, x):
                return c + (x @ x).sum(), ()
            def f(xs):
                return jax.lax.scan(body, jnp.float32(0), xs)[0]
            xs = jax.ShapeDtypeStruct((6, 8, 128, 128), jnp.float32)
            sh = NamedSharding(mesh, P(None, "d"))
            comp = jax.jit(f, in_shardings=sh).lower(xs).compile()
            a = analyze_hlo(comp.as_text())
            ar = a.collectives.get("all-reduce", {"count": 0})
            assert ar["count"] >= 6, a.collectives  # one per scan step
            print("OK", a.collectives)
        """)
        out = subprocess.run([sys.executable, "-c", script], cwd="/root/repo",
                             capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout


class TestDryRunSmoke:
    """End-to-end dry-run of one real cell on the production mesh (512
    placeholder devices) in a subprocess."""

    def test_one_cell_compiles(self):
        script = textwrap.dedent("""
            import sys
            sys.path.insert(0, "src")
            from repro.launch.dryrun import run_cell  # sets XLA_FLAGS first
            rec = run_cell("seamless-m4t-medium", "train_4k",
                           multi_pod=False, verbose=False)
            assert rec["status"] == "ok", rec
            assert rec["n_chips"] == 256
            assert rec["flops_per_device"] > 0
            assert rec["collective_bytes_per_device"] > 0
            assert not rec["unresolved_trips"]
            rec2 = run_cell("seamless-m4t-medium", "decode_32k",
                            multi_pod=True, verbose=False)
            assert rec2["status"] == "ok" and rec2["n_chips"] == 512
            print("OK")
        """)
        out = subprocess.run([sys.executable, "-c", script], cwd="/root/repo",
                             capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout


class TestTuneCLI:
    """The ACTS-over-the-runtime launcher: probe mode end to end."""

    def test_probe_mode(self):
        script_out = subprocess.run(
            [sys.executable, "-m", "repro.launch.tune",
             "--arch", "seamless-m4t-medium", "--shape", "decode_32k",
             "--probe", "kv_seq_shard=true"],
            cwd="/root/repo", capture_output=True, text=True, timeout=560,
            env={**__import__("os").environ, "PYTHONPATH": "src"})
        assert script_out.returncode == 0, script_out.stderr[-2000:]
        import json as _json

        # the verbose [sut_jax] line also contains braces; the JSON report
        # starts at the first line that is exactly "{"
        txt = script_out.stdout
        blob = _json.loads(txt[txt.index("\n{") + 1:])
        assert blob["arch"] == "seamless-m4t-medium"
        assert blob["config"]["kv_seq_shard"] is True
        assert blob["value_s"] > 0
        assert blob["metrics"]["dominant"] in ("compute", "memory",
                                               "collective")

    def test_probe_failure_exits_nonzero(self):
        """A probe of a config that fails to compile is an error the user
        sees: non-zero exit and the error text, not a silent ``inf``."""
        script = textwrap.dedent("""
            import sys
            import repro.launch.dryrun as dryrun

            def boom(*a, **k):
                raise RuntimeError("planted compile failure")

            dryrun.run_cell = boom
            from repro.launch.tune import main
            sys.exit(main(["--arch", "seamless-m4t-medium",
                           "--shape", "decode_32k", "--probe", ""]))
        """)
        out = subprocess.run(
            [sys.executable, "-c", script], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=240,
            env={**os.environ, "PYTHONPATH": "src"})
        assert out.returncode == 1, out.stderr[-2000:]
        assert "planted compile failure" in out.stderr
        assert "value_s" not in out.stdout


class TestDryRunSUT:
    """``JaxDryRunSUT``: search candidates lose with ``inf``; a measured
    config's compile error propagates."""

    def test_search_scores_inf_measure_raises(self, monkeypatch):
        import types

        from repro.core.sut_jax import JaxDryRunSUT, knob_space

        def boom(*a, **k):
            raise RuntimeError("planted compile failure")

        monkeypatch.setitem(sys.modules, "repro.launch.dryrun",
                            types.SimpleNamespace(run_cell=boom))
        sut = JaxDryRunSUT("seamless-m4t-medium", "decode_32k")
        config = knob_space("decode").default_config()
        metric = sut.test(config)
        assert metric.value == float("inf")
        assert "planted compile failure" in metric.metrics["error"]
        with pytest.raises(RuntimeError, match="planted compile failure"):
            sut.measure(config)

    def test_skipped_cell_raises_on_measure(self, monkeypatch):
        import types

        from repro.core.sut_jax import JaxDryRunSUT, knob_space

        monkeypatch.setitem(
            sys.modules, "repro.launch.dryrun",
            types.SimpleNamespace(run_cell=lambda *a, **k: {
                "status": "skipped", "reason": "not applicable"}))
        sut = JaxDryRunSUT("gemma-7b", "long_500k")
        config = knob_space("decode").default_config()
        assert sut.test(config).value == float("inf")
        with pytest.raises(RuntimeError, match="not applicable"):
            sut.measure(config)
