"""Published peaks of each accelerator the benchmark runs on, by the
``device_kind`` JAX reports. A device missing here is an error, never a
default.

TPU v5e ("TPU v5 lite"): 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM —
Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
