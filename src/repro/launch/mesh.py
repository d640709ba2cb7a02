"""Mesh builders: the one place a ``jax.sharding.Mesh`` is constructed.

Every mesh uses ``AxisType.Auto`` axes.  ``jax.make_mesh`` defaults to
``Explicit`` axes, under which ``with_sharding_constraint`` (the body of
``dist.sharding.logical_shard``) refuses to name any axis, so a mesh built
without explicit axis types breaks every sharded forward.

Functions (not module-level constants) so importing never touches JAX
device state — required because dryrun.py must set
XLA_FLAGS=--xla_force_host_platform_device_count before first JAX init.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (data, model) single pod; 2×16×16 (pod, data, model) multi-pod.

    v5e: 256 chips/pod; the multi-pod mesh proves the "pod" axis shards
    (DCN-connected pods).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Tiny mesh over the actually-present devices (tests / examples)."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    return make_mesh((n // mp, mp), ("data", "model"))
