"""Published peaks per chip, keyed by JAX's ``device_kind``.

TPU v5e (JAX reports ``TPU v5 lite``): Google Cloud documentation, "TPU
v5e", per chip: 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM.
A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/benchlib/"
                       f"peaks.py with their source") from None
