"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a
roofline share against a guessed peak is not a measurement.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture): per chip
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
    "TPU v5 lite": dict(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12,
                        hbm_bytes=16e9,
                        source='Google Cloud documentation, "TPU v5e"'),
}


def peaks(device_kind: str) -> dict:
    """The peak table entry of ``device_kind``; raises ``KeyError`` for a
    device the table does not list."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add them to bench/peaks.py with "
                       f"their source") from None
