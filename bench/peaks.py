"""Peaks of one chip, by ``device_kind`` as jax reports it.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bfloat16,
16 GiB of HBM at 819 GB/s per chip. A kind that is not here is an
error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks are known for device kind {device_kind!r}; add them "
            "to bench/peaks.py with their source") from None
