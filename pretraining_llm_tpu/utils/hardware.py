"""Per-chip peak FLOPs table for MFU accounting.

The reference prints only wall-clock deltas (`train_transformer.py:98-101`);
MFU = achieved_flops / peak_flops is the BASELINE.json headline metric, so the
framework needs to know what "peak" is for the chip it runs on.

Published bf16 peak matmul throughput per chip (Google Cloud TPU docs).
"""

from __future__ import annotations

import jax

_PEAK_BF16_FLOPS = {
    # substring of jax.Device.device_kind (lowercased) -> FLOP/s
    "v6e": 918e12,
    "trillium": 918e12,
    "v5p": 459e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 46e12,
}

_NOMINAL_CPU_FLOPS = 1e11  # tests only: keeps MFU math finite off-chip


def device_peak_flops(device: jax.Device | None = None) -> float:
    """Peak bf16 FLOP/s for one chip; a nominal constant on CPU.

    An accelerator that is not in the table raises: an MFU against a
    made-up peak is worse than none."""
    if device is None:
        device = jax.devices()[0]
    kind = device.device_kind.lower()
    for key, flops in _PEAK_BF16_FLOPS.items():
        if key in kind:
            return flops
    if device.platform == "cpu":
        return _NOMINAL_CPU_FLOPS
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to utils/hardware.py with "
        "its source"
    )
