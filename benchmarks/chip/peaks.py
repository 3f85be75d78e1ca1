"""Published peaks per chip, keyed by `device_kind` as JAX reports it.

TPU v5e ("TPU v5 lite"): 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM, per
Google Cloud's "TPU v5e" documentation. A kind not in the table is an
error, never a default.
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
                       f"{device_kind!r}; add them to peaks.py") from None
