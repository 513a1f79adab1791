"""Published per-chip peaks, keyed by ``device_kind``. Unknown device = error.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(
            f"no published peak {what!r} for device kind {device_kind!r}; "
            f"add it to benchmark/harness/peaks.py with its source"
        ) from None
