"""Published peaks of the accelerators the benchmark runs on, keyed by
JAX's ``device_kind``. A kind that is not in the table is an error: a
share of a peak is never computed against a guessed one."""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f"; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
