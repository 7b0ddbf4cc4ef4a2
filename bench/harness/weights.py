"""Random weights drawn on the device, in one jitted call, from the seed.

The tree is laid out as the program takes it: leaves stacked over
layers under ``blocks/0_attn``, matmul weights in the type they are served in
(``torch_dtype``), norm scales in float32.  The reference reads the same
tree; nothing in it is made by the program.  Scales keep activations
near unit size at any width: matmul weights ``N(0, 1/fan_in)``, norm
scales ``1 + N(0, 0.05^2)``, biases ``N(0, 0.1^2)``, and the embedding
``N(0, 1/d)`` (the program multiplies it by ``sqrt(d)``).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import numpy as np

from .counts import Dims

def layout(dims: Dims, qkv_bias: bool,
           dtype: str = "bfloat16") -> Dict[str, Any]:
    L, d, H, KV, Dh, F, V = (dims.layers, dims.d, dims.heads, dims.kv_heads,
                             dims.head_dim, dims.ff, dims.vocab)
    bf = dtype
    attn = {
        "wq": ((L, d, H, Dh), bf, "normal", 1 / math.sqrt(d)),
        "wk": ((L, d, KV, Dh), bf, "normal", 1 / math.sqrt(d)),
        "wv": ((L, d, KV, Dh), bf, "normal", 1 / math.sqrt(d)),
        "wo": ((L, H, Dh, d), bf, "normal", 1 / math.sqrt(H * Dh)),
    }
    if qkv_bias:
        attn["bq"] = ((L, H, Dh), bf, "normal", 0.1)
        attn["bk"] = ((L, KV, Dh), bf, "normal", 0.1)
        attn["bv"] = ((L, KV, Dh), bf, "normal", 0.1)
    mlp = {"wi": ((L, d, F), bf, "normal", 1 / math.sqrt(d)),
           "wo": ((L, F, d), bf, "normal", 1 / math.sqrt(F))}
    if dims.gated:
        mlp["wg"] = ((L, d, F), bf, "normal", 1 / math.sqrt(d))
    norm = ((L, d), "float32", "norm", 0.05)
    return {
        "embed": ((V, d), bf, "normal", 1 / math.sqrt(d)),
        "blocks": {"0_attn": {"ln1": norm, "attn": attn, "ln2": norm,
                              "mlp": mlp}},
        "final_norm": ((d,), "float32", "norm", 0.05),
        "lm_head": ((d, V), bf, "normal", 1 / math.sqrt(d)),
    }


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 4 and isinstance(x[0], tuple)


def root_key(seed: int):
    """A JAX key from any whole number, however many bits it has."""
    import jax

    a, b = np.random.SeedSequence(int(seed) % 2 ** 64).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(a) & 0x7FFFFFFF),
                              int(b) & 0x7FFFFFFF)


def drawer(tree: Dict[str, Any], out_shardings=None):
    """A jitted ``key -> weights`` for ``tree`` (one compiled program)."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=_is_leaf)

    def draw(key):
        out = []
        for i, (shape, dtype, kind, std) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            dt = jnp.dtype(dtype)
            z = jax.random.normal(k, shape, dt)
            if kind == "norm":
                out.append(1 + z * jnp.asarray(std, dt))
            else:
                out.append(z * jnp.asarray(std, dt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw, out_shardings=out_shardings)


def shapes(tree: Dict[str, Any]):
    """``jax.ShapeDtypeStruct`` of every leaf."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l[0], jnp.dtype(l[1])), tree,
        is_leaf=_is_leaf)
