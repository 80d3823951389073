"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state. Single pod: 16x16 = 256 chips (data x model). Multi-pod:
2x16x16 = 512 chips (pod x data x model); the pod axis is pure DP for serving
and the outer gradient-reduction tier for training. Scaling to more pods is a
mesh-shape change only. Every axis is ``AxisType.Auto``: shardings come from
the params' specs and XLA propagation.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
