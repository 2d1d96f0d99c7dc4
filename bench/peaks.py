"""Peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]
