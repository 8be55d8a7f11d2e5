"""Published peaks of each chip, keyed by ``device_kind`` as JAX reports
it. A device that is not in the table is an error, never a default.

TPU v5e ("TPU v5 lite"): 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of
HBM at 819 GB/s -- Google Cloud documentation, "TPU v5e"
(https://cloud.google.com/tpu/docs/v5e).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to peaks.py with their "
                       f"source") from None
