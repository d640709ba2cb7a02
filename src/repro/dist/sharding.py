"""Logical-axis sharding: the single source of truth for how tensors land
on a mesh (DESIGN.md §5).

Model code never names mesh axes.  Parameters carry *logical* per-dim names
(``Px`` leaves in models/layers.py: ``d_model_w``, ``heads``, ``ff``, …) and
activations are pinned with ``logical_shard(x, "batch", "seq", "d_model")``.
This module owns the table that maps logical names to physical mesh axes —
change the table, re-lower, and the whole system (train step, decode step,
checkpoints) moves to the new layout.

Layout policy (single pod, ``(data, model)``):

  * ``batch`` / ``capacity``  → ``data``        (DP / MoE buffer rows)
  * ``d_model_w``             → ``data``        (FSDP: weight-stationary dim)
  * ``heads`` ``kv_heads`` ``ff`` ``vocab`` ``experts`` ``state``
                              → ``model``       (TP / EP)
  * ``seq`` ``frames`` ``d_model`` ``layers``   → replicated

Multi-pod (``(pod, data, model)``) extends the DP/FSDP entries to
``("pod", "data")`` — the pod axis only ever carries batch-like or
FSDP-sharded dims, so DCN traffic stays gradient/all-gather shaped.

``logical_shard`` is *advisory*: under an active ``use_mesh`` it applies
``with_sharding_constraint`` (dropping any per-dim entry whose mesh-axis
product does not divide the dim — GSPMD would otherwise have to pad weight
shards); with no mesh it returns its input unchanged, so pure single-host
code paths never touch sharding machinery.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "Axis", "default_rules", "spec_for_axes", "batch_spec",
    "use_mesh", "current_mesh", "logical_shard",
    "manual_axes", "in_manual_axes", "manual_axis_info",
]

# A rule value: one mesh axis, a tuple of mesh axes, or None (replicate).
Axis = Optional[Union[str, Tuple[str, ...]]]

_DP_SINGLE = ("data",)
_DP_MULTI = ("pod", "data")


def default_rules(multi_pod: bool = False) -> Dict[str, Axis]:
    """Logical-name → mesh-axis table for the production meshes.

    ``multi_pod=False`` targets the 16×16 ``(data, model)`` pod;
    ``multi_pod=True`` the 2×16×16 ``(pod, data, model)`` slice.  Unknown
    logical names (and ``None``) always replicate, so new model code can
    introduce a name before the table learns how to shard it.
    """
    dp: Axis = _DP_MULTI if multi_pod else "data"
    return {
        # activations
        "batch": dp,
        "seq": None,
        "frames": None,
        "d_model": None,
        "capacity": dp,
        # weights (and the activation dims that mirror them)
        "d_model_w": dp,
        "heads": "model",
        "kv_heads": "model",
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        "state": "model",
        "layers": None,
        # the explicit shard axis of k-sharded serving payloads
        # (serve/sharded.py): each entry is one contiguous in-feature
        # block's planar repack, so the axis is pure tensor parallelism
        "kshard": "model",
    }


# ---------------------------------------------------------------------------
# Active-mesh context
# ---------------------------------------------------------------------------

_LOCAL = threading.local()


def _stack():
    if not hasattr(_LOCAL, "meshes"):
        _LOCAL.meshes = []
    return _LOCAL.meshes


@contextlib.contextmanager
def use_mesh(mesh):
    """Make ``mesh`` the active mesh for logical_shard / spec_for_axes.

    Nestable; thread-local (each pytest-xdist worker / engine thread sees
    only its own mesh).  Model code reads it via :func:`current_mesh`.
    """
    _stack().append(mesh)
    try:
        yield mesh
    finally:
        _stack().pop()


def current_mesh():
    """The innermost ``use_mesh`` mesh, or None outside any context."""
    s = _stack()
    return s[-1] if s else None


def batch_spec(mesh=None) -> Tuple[str, ...]:
    """The data-parallel axis tuple of ``mesh`` (pod axis first when
    present) — what the leading batch dim of inputs shards over."""
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return _DP_SINGLE
    return tuple(a for a in _DP_MULTI if a in mesh.axis_names)


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def _rules_for_active_mesh() -> Dict[str, Axis]:
    mesh = current_mesh()
    return default_rules(mesh is not None and "pod" in mesh.axis_names)


def spec_for_axes(axes: Sequence[Optional[str]],
                  rules: Optional[Dict[str, Axis]] = None) -> P:
    """Per-dim logical names → PartitionSpec, never repeating a mesh axis.

    A mesh axis is assigned to the first dim that claims it; later claims
    in the same tensor degrade to replicated (e.g. a square ``(lru, lru)``
    weight whose dims both resolve to ``model``).  With ``rules=None`` the
    table is inferred from the active mesh (multi-pod iff it has a ``pod``
    axis).
    """
    if rules is None:
        rules = _rules_for_active_mesh()
    used = set()
    entries = []
    for name in axes:
        rule = rules.get(name) if name is not None else None
        if rule is None:
            entries.append(None)
            continue
        names = rule if isinstance(rule, tuple) else (rule,)
        free = tuple(n for n in names if n not in used)
        used.update(free)
        entries.append(free[0] if len(free) == 1 else (free or None))
    return P(*entries)


def _axis_product(mesh, entry) -> int:
    if entry is None:
        return 1
    names = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


@contextlib.contextmanager
def manual_axes(**info):
    """Mark that tracing is inside a ``shard_map`` body (per-device view).

    ``with_sharding_constraint`` is a global-view annotation and is
    invalid on the per-device values a shard_map body manipulates, so
    while this context is active :func:`logical_shard` is a strict no-op
    even under an active ``use_mesh``.  Thread-local and re-entrant, like
    the mesh stack.

    ``info`` is free-form metadata model code can read back with
    :func:`manual_axis_info` — the k-sharded serving path stores the mesh
    axis name, static shard count, and whether the KV cache arrives
    shard-local (serve/sharded.py, DESIGN.md §13).
    """
    stack = getattr(_LOCAL, "manual_stack", None)
    if stack is None:
        stack = _LOCAL.manual_stack = []
    stack.append(dict(info))
    try:
        yield
    finally:
        stack.pop()


def in_manual_axes() -> bool:
    """True while tracing inside a :func:`manual_axes` scope."""
    return bool(getattr(_LOCAL, "manual_stack", None))


def manual_axis_info() -> Optional[Dict[str, object]]:
    """The innermost :func:`manual_axes` metadata dict, or None."""
    stack = getattr(_LOCAL, "manual_stack", None)
    return stack[-1] if stack else None


def logical_shard(x, *axes: Optional[str]):
    """Pin ``x`` to the active mesh by logical axis names; no-op otherwise.

    Strictness contract (tested): with no active mesh this returns ``x``
    itself — not a copy, not a traced identity — so the single-device path
    is bit-for-bit the untouched computation.  Under a mesh, per-dim
    entries are dropped when (a) the named mesh axes are absent from the
    active mesh or (b) their size product does not divide the dim (e.g. a
    2-kv-head cache on a 4-way model axis, which then replicates).
    Inside a :func:`manual_axes` scope (tracing a shard_map body) it is
    likewise the strict identity.
    """
    mesh = current_mesh()
    if mesh is None or in_manual_axes():
        return x
    spec = spec_for_axes(axes)
    entries = []
    for dim, entry in zip(x.shape, tuple(spec)):
        if entry is not None:
            names = entry if isinstance(entry, tuple) else (entry,)
            if any(a not in mesh.axis_names for a in names) \
                    or dim % _axis_product(mesh, entry):
                entry = None
        entries.append(entry)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*entries)))
