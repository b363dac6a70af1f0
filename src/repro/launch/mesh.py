"""Production mesh construction (DESIGN.md §4).

A FUNCTION, not a module constant: importing this module must never touch
jax device state (smoke tests see 1 device; only dryrun.py forces 512).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is pure
    data parallelism over the slower inter-pod links."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_probe_mesh(n_devices: int | None = None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    model = 1
    for m in (8, 4, 2, 1):
        if n % m == 0:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
