"""The Pallas kernels compile for a TPU v5e, with no chip attached.

Each test AOT-compiles one kernel for a *described* ``v5e:2x2`` topology
at published widths (Qwen2.5-32B for the attention kernels and rmsnorm,
xLSTM-350M's mLSTM for GLA) and checks that the compiled program holds
the Mosaic kernel (``tpu_custom_call``): the chip's compiler refuses
block shapes, scratch layouts and primitives that interpret mode accepts.
The sharded paged-kernel tests compile the served decode attention under
tensor-parallel and replica meshes built from the described chips.

The topology is described inside module-scoped fixtures, never at import:
only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.configs import get_config

SEMANTICS = (None, "parallel")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _qwen():
    cfg = get_config("qwen2.5-32b")
    return cfg.padded_heads, cfg.n_kv_heads, cfg.head_dim_, cfg.d_model


@pytest.mark.parametrize("semantics", SEMANTICS)
def test_paged_attention(one_chip, semantics):
    """At the chat cell's shapes (32 slots of 64 page groups of 64 tokens,
    a 641-group pool), whole and as one TP (1, 4) shard sees it (KV 2):
    one Mosaic kernel each."""
    from repro.kernels.paged_attention import paged_flash_decode_pallas

    H, KV, D, _ = _qwen()
    B, T, maxg, G = 32, 64, 64, 641
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    for kv in (KV, KV // 4):
        h = H // KV * kv
        text = _compiled_text(
            lambda q, k, v, pt, ln: paged_flash_decode_pallas(
                q, k, v, pt, ln, dimension_semantics=semantics),
            s((B, h, D)), s((G, kv, T, D)), s((G, kv, T, D)),
            s((B, maxg), jnp.int32), s((B,), jnp.int32))
        assert text.count("tpu_custom_call") == 1, kv


@pytest.mark.parametrize("semantics", SEMANTICS)
def test_decode_attention(one_chip, semantics):
    from repro.kernels.decode_attention import flash_decode_pallas

    H, KV, D, _ = _qwen()
    B, S = 8, 2048
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v, n: flash_decode_pallas(
            q, k, v, n, block_kv=256, dimension_semantics=semantics),
        s((B, H, D)), s((B, S, KV, D)), s((B, S, KV, D)), s((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("semantics", SEMANTICS)
def test_flash_attention(one_chip, semantics):
    from repro.kernels.flash_attention import flash_attention_pallas

    H, KV, D, _ = _qwen()
    S = 512
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                           sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_attention_pallas(
            q, k, v, causal=True, block_q=128, block_kv=128,
            dimension_semantics=semantics),
        s((1, S, H, D)), s((1, S, KV, D)), s((1, S, KV, D)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("semantics", SEMANTICS)
def test_rmsnorm(one_chip, semantics):
    from repro.kernels.rmsnorm import rmsnorm_pallas

    d_model = _qwen()[3]
    text = _compiled_text(
        lambda x, sc: rmsnorm_pallas(x, sc, block_rows=256,
                                     dimension_semantics=semantics),
        jax.ShapeDtypeStruct((512, d_model), jnp.bfloat16,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((d_model,), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("semantics", SEMANTICS)
def test_gla(one_chip, semantics):
    """xLSTM-350M's mLSTM: 4 heads of 512, values widened by the
    normalizer column (dv = 513), float32, the config's chunk."""
    from repro.kernels.gla import gla_pallas
    from repro.models.xlstm import _mdims

    cfg = get_config("xlstm-350m")
    _, H, dh = _mdims(cfg)
    S = 2 * cfg.ssm_chunk
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v, g: gla_pallas(q, k, v, g, chunk=cfg.ssm_chunk,
                                      dimension_semantics=semantics),
        s((1, S, H, dh)), s((1, S, H, dh)), s((1, S, H, dh + 1)),
        s((1, S, H)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mesh_shape", [(1, 4), (4, 1)],
                         ids=["tp_1x4", "replicas_4x1"])
def test_paged_attention_sharded(topo, monkeypatch, mesh_shape):
    """GSPMD cannot partition a Mosaic kernel: under a mesh the served
    decode attention runs it per shard (``shard_map``), heads split on
    the model axis, slots on the data axis."""
    import repro.kernels.ops as ops
    from repro.dist.sharding import RULE_PRESETS, axis_rules
    from repro.models.attention import _paged_kernel_per_shard

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    H, KV, D, _ = _qwen()
    data, model = mesh_shape
    B, T, maxg = 2 * data, 64, 17
    G = B * maxg + 1
    mesh = Mesh(np.array(topo.devices).reshape(mesh_shape),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    ns = lambda *spec: NamedSharding(mesh, PartitionSpec(*spec))
    heads = "model" if model > 1 else None
    rows = "data" if data > 1 else None
    s = lambda shape, sh, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=sh)
    with axis_rules(RULE_PRESETS["serve_tp"], mesh):
        text = _compiled_text(
            _paged_kernel_per_shard,
            s((B, H, D), ns(rows, heads)),
            s((G, KV, T, D), ns(None, heads)),
            s((G, KV, T, D), ns(None, heads)),
            s((B, maxg), ns(rows), jnp.int32),
            s((B,), ns(rows), jnp.int32))
    assert "tpu_custom_call" in text
