"""Published peaks of one accelerator chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
There is deliberately no override: a device that is not in the table is an
error, never a default.
"""
from __future__ import annotations

from typing import Dict

__all__ = ["PEAKS", "peaks_for"]

_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
}

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
