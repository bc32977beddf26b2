"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` as JAX reports it.  A device that is not here is an error."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e" (system architecture table):
    # 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
