"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before *any* jax
initialization, and smoke tests must keep seeing 1 device.

Every mesh is built with ``AxisType.Auto`` axes: the model code shards
through ``with_sharding_constraint`` annotations and GSPMD propagation
(``repro.dist.sharding.constrain``), which explicit-axis meshes — the
``jax.make_mesh`` default — refuse.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "describe_mesh"]


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment: one v5e pod (16x16) or two pods (2x16x16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(data: int, model: int, pod: int = 1):
    """An arbitrary (pod×)data×model mesh — used by ACTS mesh-factorization
    knobs and by CPU-scale tests (e.g. 2x2 over 4 host devices)."""
    if pod > 1:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def describe_mesh(mesh) -> str:
    return "x".join(f"{k}={v}" for k, v in mesh.shape.items())
