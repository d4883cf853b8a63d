"""Mesh construction for this host.

A function, never a module-level constant, so importing this module
never touches jax device state: a caller may still set
``--xla_force_host_platform_device_count`` before jax initializes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_local_mesh(model_axis: int = 1):
    """Whatever this host has (tests / reduced runs)."""
    n = len(jax.devices())
    data = max(n // model_axis, 1)
    return jax.make_mesh((data, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
