"""Published peaks of one chip, keyed by the exact `device_kind` JAX reports.

Source: Google Cloud documentation, "TPU v5e" system architecture (197
TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip); the bf16
and bytes/s figures agree with `aios_tpu/obs/devprof.py DEVICE_PEAKS`, which
lacks the int8 peak. A device that is not here is an error, never a default.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_of(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            "benchmark/harness/peaks.py with its source"
        ) from None
