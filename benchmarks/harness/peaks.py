"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error, not
a default. Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
bf16, 16 GB of HBM at 819 GB/s, per chip)."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to benchmarks/harness/"
                       f"peaks.py with its source")
    return PEAKS[device_kind]
