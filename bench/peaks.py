"""Peaks of one chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
A kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

from typing import Dict

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)} ({SOURCE})") from None
