"""Model substrate: parameter definitions, init, norms, rotary embeddings.

Parameters are declared as ``ParamDef`` trees (shape + logical axes + init),
which gives three views of the same model for free:

* ``init_params``      — materialized weights (smoke tests, real training),
* ``abstract_params``  — ShapeDtypeStructs (the multi-pod dry-run: no
                         allocation, exactly the shannon/kernels pattern),
* ``param_specs``      — PartitionSpec tree under the active sharding rules
                         (the knob surface ACTS tunes).
"""
from __future__ import annotations

import functools
import math
import zlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.dist.sharding import AxisRules, spec_for_shape

__all__ = [
    "ParamDef",
    "stack_defs",
    "init_params",
    "abstract_params",
    "param_specs",
    "count_def_params",
    "rms_norm",
    "rope_freqs",
    "apply_rope",
    "cross_entropy_loss",
    "dtype_of",
]


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# ParamDef trees
# ---------------------------------------------------------------------------
InitFn = Callable[[jax.Array, Tuple[int, ...], Any], jax.Array]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _scaled_normal(key, shape, dtype, std):
    """``std``-scaled standard normal draw cast to ``dtype``, as ONE
    compiled program: the float32 draw, the scale and the cast fuse, so
    a bfloat16 leaf never has a float32 twin of its full size on the
    device (at published widths one stacked MLP leaf would be 4.5 GB in
    float32).  One compilation per (shape, dtype), shared by every model
    with that leaf shape."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def normal_init(std: float) -> InitFn:
    def init(key, shape, dtype):
        return _scaled_normal(key, tuple(shape), dtype, std)

    return init


def fan_in_init(axis: int = -2) -> InitFn:
    """Lecun-normal on the fan-in dimension(s): std = 1/sqrt(fan_in)."""

    def init(key, shape, dtype):
        fan_in = shape[axis] if len(shape) >= 2 else shape[0]
        std = 1.0 / math.sqrt(max(fan_in, 1))
        return _scaled_normal(key, tuple(shape), dtype, std)

    return init


def zeros_init() -> InitFn:
    return lambda key, shape, dtype: jnp.zeros(shape, dtype)


def ones_init() -> InitFn:
    return lambda key, shape, dtype: jnp.ones(shape, dtype)


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis names, len == len(shape)
    init: InitFn
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")


def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _map_defs(fn, tree):
    return jax.tree_util.tree_map(fn, tree, is_leaf=_is_def)


def stack_defs(tree, n: int, axis_name: str = "layer"):
    """Add a leading stacking dim (scan-over-superblocks parameter layout)."""

    def stack(d: ParamDef) -> ParamDef:
        return ParamDef((n,) + d.shape, (axis_name,) + d.axes, d.init, d.dtype)

    return _map_defs(stack, tree)


def _path_key(root: jax.Array, path) -> jax.Array:
    label = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
    return jax.random.fold_in(root, zlib.crc32(label.encode()) & 0x7FFFFFFF)


def init_params(tree, rng: jax.Array):
    """Materialize a ParamDef tree (deterministic per-leaf keys by path)."""

    def init_leaf(path, d: ParamDef):
        return d.init(_path_key(rng, path), d.shape, d.dtype)

    return jax.tree_util.tree_map_with_path(init_leaf, tree, is_leaf=_is_def)


def abstract_params(tree):
    return _map_defs(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), tree)


def param_specs(tree, rules: AxisRules, mesh):
    return _map_defs(
        lambda d: spec_for_shape(d.shape, d.axes, rules, mesh), tree
    )


def count_def_params(tree) -> int:
    leaves = jax.tree_util.tree_leaves(tree, is_leaf=_is_def)
    return sum(int(np.prod(d.shape)) for d in leaves)


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def rope_freqs(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for given integer positions: (..., head_dim/2)."""
    half = head_dim // 2
    freq = (theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freq  # (..., half)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (B, S, D/2) or (S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:  # (S, half) -> broadcast over batch and heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:  # (B, S, half)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    x32_1, x32_2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    r1 = x32_1 * cos - x32_2 * sin
    r2 = x32_2 * cos + x32_1 * sin
    return jnp.concatenate([r1, r2], axis=-1).astype(x.dtype)


def cross_entropy_loss(
    logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None,
    z_loss: float = 0.0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Token-mean cross entropy in f32 with optional z-loss regularizer."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(logz)
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    acc = ((logits.argmax(-1) == labels) * mask).sum() / denom
    return loss, {"loss": loss, "accuracy": acc, "tokens": denom}
