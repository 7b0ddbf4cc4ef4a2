"""Random weights drawn on the device, in one jitted call, from the seed.

The tree is the configuration's family's ``layout(cfg)``
(``bench/families/<family>.py``): each leaf ``(shape, dtype, kind,
std)``, laid out as the program takes it.  A ``normal`` leaf is drawn
``N(0, std^2)``, a ``norm`` leaf ``1 + N(0, std^2)``.  The reference
reads the same tree; nothing in it is made by the program.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


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
