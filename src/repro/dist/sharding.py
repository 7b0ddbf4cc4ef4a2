"""Logical-axis sharding: named-rule mapping from model axes to mesh axes.

Models annotate every parameter/activation dimension with a *logical* axis
name ("batch", "heads", "ff", ...).  An ``AxisRules`` table maps logical
axes to physical mesh axes ("pod", "data", "model"); swapping the table
re-shards the whole program without touching model code — this is the knob
surface the ACTS tuner drives (``RunKnobs.rules_preset`` and friends).

Safety properties of ``spec_for_shape`` (what makes *any* ruleset a valid
configuration rather than a compile error):

* a mesh axis absent from the active mesh is silently dropped (the same
  rules work on 16x16 and 2x16x16 meshes),
* a mapping whose mesh-axis product does not divide the dimension is
  dropped entirely (e.g. 40 heads on a 16-way model axis falls back to
  replication instead of failing to lower),
* each mesh axis is used at most once per tensor (first dimension wins),
  so joint rules never produce an over-constrained spec.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Dict, Iterator, Mapping, Optional, Sequence, Tuple, Union

from jax.sharding import NamedSharding, PartitionSpec

__all__ = [
    "AxisRules",
    "DEFAULT_RULES",
    "DP_ALL_RULES",
    "KNOWN_LOGICAL_AXES",
    "REPLICATED_AXES",
    "RULE_PRESETS",
    "SERVE_REPLICAS_RULES",
    "SERVE_TP_RULES",
    "axis_rules",
    "constrain",
    "spec_for_shape",
]

# A logical axis maps to one mesh axis, a tuple of mesh axes, or None.
AxisTarget = Union[str, Tuple[str, ...], None]


def _canon_target(t: Any) -> AxisTarget:
    if t is None or isinstance(t, str):
        return t
    return tuple(t)


class AxisRules:
    """Immutable logical-axis -> mesh-axis mapping.

    ``replace`` returns a new table with the given entries overridden (or
    added; mapping to ``None`` unmaps).  ``lookup`` returns ``None`` for any
    unmapped logical axis, so rule tables stay sparse.
    """

    __slots__ = ("_rules",)

    def __init__(self, rules: Optional[Mapping[str, AxisTarget]] = None,
                 **kwargs: AxisTarget):
        merged: Dict[str, AxisTarget] = {}
        for k, v in dict(rules or {}, **kwargs).items():
            v = _canon_target(v)
            if v is not None:
                merged[k] = v
        object.__setattr__(self, "_rules", merged)

    def __setattr__(self, name, value):  # immutability guard
        raise AttributeError("AxisRules is immutable; use .replace()")

    def lookup(self, logical: Optional[str]) -> AxisTarget:
        if logical is None:
            return None
        return self._rules.get(logical)

    def replace(self, **updates: AxisTarget) -> "AxisRules":
        merged = dict(self._rules)
        for k, v in updates.items():
            v = _canon_target(v)
            if v is None:
                merged.pop(k, None)
            else:
                merged[k] = v
        return AxisRules(merged)

    def items(self):
        return self._rules.items()

    def __eq__(self, other) -> bool:
        return isinstance(other, AxisRules) and self._rules == other._rules

    def __hash__(self) -> int:
        return hash(tuple(sorted(self._rules.items())))

    def __repr__(self) -> str:
        body = ", ".join(f"{k}={v!r}" for k, v in sorted(self._rules.items()))
        return f"AxisRules({body})"


# ---------------------------------------------------------------------------
# presets
# ---------------------------------------------------------------------------
# FSDP over the data axis + tensor parallelism over the model axis: the
# production default ("fsdp_tp" in RunKnobs).
DEFAULT_RULES = AxisRules(
    batch=("pod", "data"),
    embed_fsdp="data",
    heads="model",
    kv_heads="model",
    ff="model",
    vocab="model",
    experts="model",
)

# Pure data parallelism over *every* mesh axis (batch spread over the model
# axis too; params fully replicated) — the small-model/throughput extreme.
DP_ALL_RULES = AxisRules(batch=("pod", "data", "model"))

# Serve-side presets: inference meshes are (data, model) with no pod axis,
# and serving never FSDP-shards weights (no ``embed_fsdp``) — replicas need
# the full parameter set resident per data-axis slice, and the TP split
# streams each shard's own heads/ff columns.  The ``data`` axis carries
# engine *replicas* (batch slots spread across them); the ``model`` axis is
# the tensor-parallel split of heads / ff / vocab.
SERVE_TP_RULES = AxisRules(batch="data", heads="model", kv_heads="model",
                           ff="model", vocab="model", experts="model")
SERVE_REPLICAS_RULES = AxisRules(batch="data")

RULE_PRESETS: Dict[str, AxisRules] = {
    "dp": AxisRules(batch=("pod", "data")),
    "dp_all": DP_ALL_RULES,
    # fsdp_all spreads the batch over the model axis too (no TP), sharding
    # params across every axis — the regression the qwen hillclimb hit.
    "fsdp_all": AxisRules(batch=("pod", "data", "model"),
                          embed_fsdp=("data", "model")),
    "tp": AxisRules(batch=("pod", "data"), heads="model", kv_heads="model",
                    ff="model", vocab="model", experts="model"),
    "fsdp_tp": DEFAULT_RULES,
    "serve_tp": SERVE_TP_RULES,
    "serve_replicas": SERVE_REPLICAS_RULES,
}

# Logical axes that are *deliberately* never mapped by any preset: they
# must stay replicated (sequence positions interleave through KV caches;
# head_dim/conv_dim/cap tiles feed kernels whole).  ``constrain`` calls
# naming an axis outside the preset-mapped or deliberately-replicated
# sets silently replicate — the ``constrain-unknown-axis`` lint rule
# flags them against this registry.
REPLICATED_AXES = frozenset({
    "seq", "seq_res", "embed", "head_dim", "cap", "expert_ff", "conv_dim",
})

KNOWN_LOGICAL_AXES = REPLICATED_AXES | frozenset(
    axis for rules in RULE_PRESETS.values() for axis, _ in rules.items())


# ---------------------------------------------------------------------------
# shape -> PartitionSpec
# ---------------------------------------------------------------------------
def _normalize_entries(entries: Sequence[Any]) -> Tuple[Any, ...]:
    out = []
    for e in entries:
        if isinstance(e, (list, tuple)) and len(e) == 1:
            out.append(e[0])
        elif isinstance(e, list):
            out.append(tuple(e))
        else:
            out.append(e)
    while out and out[-1] is None:
        out.pop()
    return tuple(out)


class _SemanticSpec(PartitionSpec):
    """A PartitionSpec comparing by *meaning*, not entry spelling.

    ``PartitionSpec`` equality is positional, so ``P("data") !=
    P("data", None)`` even though they shard identically.  Specs
    produced by ``spec_for_shape`` normalize single-axis tuples and
    ignore trailing ``None`` entries on comparison (and in their hash),
    matching how ``NamedSharding`` interprets them.  Instances are built
    by ``PartitionSpec``'s own constructor.
    """

    def __eq__(self, other) -> bool:
        if isinstance(other, (PartitionSpec, tuple)):
            return _normalize_entries(self) == _normalize_entries(other)
        return NotImplemented

    def __ne__(self, other) -> bool:
        eq = self.__eq__(other)
        return NotImplemented if eq is NotImplemented else not eq

    def __hash__(self) -> int:
        return hash(_normalize_entries(self))


def spec_for_shape(
    shape: Sequence[int],
    axes: Sequence[Optional[str]],
    rules: AxisRules,
    mesh: Any = None,
) -> PartitionSpec:
    """PartitionSpec for a tensor of ``shape`` with logical ``axes``.

    ``mesh`` only needs a ``.shape`` mapping of axis name -> size (a real
    ``jax.sharding.Mesh`` or any duck-typed stand-in).  See the module
    docstring for the drop/fallback rules.
    """
    if len(shape) != len(axes):
        raise ValueError(f"shape {tuple(shape)} vs axes {tuple(axes)}")
    mesh_shape: Mapping[str, int] = dict(getattr(mesh, "shape", None) or {})
    used: set = set()
    entries = []
    for dim, logical in zip(shape, axes):
        target = rules.lookup(logical)
        if target is None:
            entries.append(None)
            continue
        cand = (target,) if isinstance(target, str) else tuple(target)
        picked = []
        size = 1
        for a in cand:
            n = mesh_shape.get(a)
            if n is None or a in used:
                continue  # absent from mesh / already used by an earlier dim
            picked.append(a)
            size *= int(n)
        if not picked or size <= 1 or dim % size:
            entries.append(None)  # divisibility fallback: replicate
            continue
        used.update(picked)
        entries.append(picked[0] if len(picked) == 1 else tuple(picked))
    while entries and entries[-1] is None:
        entries.pop()
    return _SemanticSpec(*entries)


# ---------------------------------------------------------------------------
# activation constraints (the `constrain` the model code calls)
# ---------------------------------------------------------------------------
class _ActiveRules(threading.local):
    def __init__(self):
        self.stack = []  # list of (AxisRules, mesh)


_ACTIVE = _ActiveRules()


@contextmanager
def axis_rules(rules: AxisRules, mesh: Any = None) -> Iterator[None]:
    """Activate a rule table (+ mesh) for ``constrain`` calls underneath.

    Tracing a jitted step inside this context attaches sharding constraints
    to every annotated activation; outside any context ``constrain`` is a
    no-op, so the same model code runs unsharded in unit tests.
    """
    _ACTIVE.stack.append((rules, mesh))
    try:
        yield
    finally:
        _ACTIVE.stack.pop()


def current_rules() -> Optional[Tuple[AxisRules, Any]]:
    return _ACTIVE.stack[-1] if _ACTIVE.stack else None


def constrain(x: Any, *axes: Optional[str]) -> Any:
    """Constrain an activation's sharding under the active axis rules.

    ``axes`` are logical names per dimension (``None`` = unsharded).  A
    no-op unless inside an ``axis_rules`` context with a mesh.
    """
    active = current_rules()
    if active is None:
        return x
    rules, mesh = active
    if mesh is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"constrain: {len(axes)} axes for rank-{x.ndim} "
                         f"array {x.shape}")
    spec = spec_for_shape(x.shape, axes, rules, mesh)
    import jax

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*spec)))
