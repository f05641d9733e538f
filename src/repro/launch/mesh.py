"""Mesh construction.

Single pod: 256 TPU v5e chips as (data=16, model=16).
Multi-pod:  2 pods = 512 chips as (pod=2, data=16, model=16) — the ``pod``
axis carries pure data parallelism across the DCN/ICI boundary.

Every mesh has ``Auto`` axes: the models place activations with
``with_sharding_constraint`` hints (``repro.sharding_hints``), which JAX
accepts only on Auto axes, whereas ``jax.make_mesh`` defaults to Explicit.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False, shape=None):
    """Default 256-chip pod is (data=16, model=16); §Perf overrides may
    re-factor the same chips (e.g. (32, 8) when an arch's head/expert
    counts don't divide 16)."""
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    elif multi_pod and len(shape) == 2:
        shape = (2, *shape)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            f"(repro.launch.dryrun does this) or on real hardware")
    return make_mesh(shape, axes, devices=devs[:n])


def make_host_mesh(model_axis: int = 1, devices: Optional[Sequence] = None):
    """(data, model) mesh over ``devices`` (default: every visible
    device), ``model_axis`` of them per model replica."""
    devs = list(devices) if devices is not None else jax.devices()
    data = len(devs) // model_axis
    return make_mesh((data, model_axis), ("data", "model"),
                     devices=devs[:data * model_axis])
