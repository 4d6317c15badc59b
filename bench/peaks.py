"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.

A device kind missing from the table is an error: no number is ever
reported against a default peak.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; KeyError names the missing kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to bench/peaks.py with "
                       f"its source") from None
