"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not in the table is an error, never a
default: a share of an unknown peak means nothing."""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB of HBM at 819 GB/s per chip.
_V5E = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e"}

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to peaks.py with their "
                       f"source") from None
