"""Published peaks of each chip, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; have {sorted(PEAKS)}") from None
