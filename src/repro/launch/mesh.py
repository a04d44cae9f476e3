"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e-256).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis only
carries data parallelism (gradient all-reduce over DCI), model/expert
parallelism stays within a pod's ICI domain.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (device count is locked at first use).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the sharding rules place
    arrays with ``with_sharding_constraint``, which only accepts specs
    over Auto axes (``jax.make_mesh`` now defaults to Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    return make_mesh((data, model), ("data", "model"))
