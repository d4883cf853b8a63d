"""Production mesh construction.

Kept as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``--xla_force_host_platform_device_count`` before any jax initialization.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Assignment mesh: single pod (16,16)=(data,model); two pods
    (2,16,16)=(pod,data,model) — 512 chips of TPU v5e."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model_axis: int = 1):
    """Whatever this host has (tests / reduced runs)."""
    n = len(jax.devices())
    data = max(n // model_axis, 1)
    return jax.make_mesh((data, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def dp_axes(mesh) -> Tuple[str, ...]:
    """The data-parallel axes present on a mesh, in (pod, data) order."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


# Published per-chip peaks (roofline denominators), keyed by
# ``jax.Device.device_kind``.  TPU v5e: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect over 4 links (50 GB/s each).
HW_BY_KIND = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,
        "hbm_bw": 819e9,                # bytes/s
        "ici_link_bw": 50e9,            # bytes/s per link
        "ici_links_per_ring": 2,        # bidirectional ring over one axis
        "hbm_bytes": 16 * 2 ** 30,
    },
}


def hw_for(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return HW_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks recorded for device kind {device_kind!r}; "
            f"add them to HW_BY_KIND with their source") from None
