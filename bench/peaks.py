"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

TPU v5e ("TPU v5 lite" to JAX): Google Cloud documentation, "TPU v5e":
197 TFLOP/s in bfloat16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s.
A kind that is not here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,          # FLOP/s
        "hbm_bw": 819e9,               # bytes/s
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them with their "
                         f"source") from None
