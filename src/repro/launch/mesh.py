"""Production mesh construction.

A function (not a module-level constant) so importing this module never touches
jax device state. Single pod: 16x16 = 256 chips (TPU v5e pod slice); multi-pod:
2 x 16 x 16 = 512 chips with a leading 'pod' DCN axis.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape=None, axes=None, devices=None):
    """Small mesh over whatever local devices exist (tests / smoke runs), or
    over ``devices`` when given — e.g. two of a host's four chips, the
    target of an elastic restart onto fewer devices."""
    n = len(jax.devices() if devices is None else devices)
    if shape is None:
        if n >= 8:
            shape, axes = (2, n // 2), ("data", "model")
        elif n > 1:
            shape, axes = (1, n), ("data", "model")
        else:
            shape, axes = (1, 1), ("data", "model")
    return _make_mesh(shape, axes, devices)
