"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in this table is an error: the benchmark has no
default peak.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" system
architecture page: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at
819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud, TPU v5e system architecture"},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"bench: no published peaks for device kind "
                         f"{device_kind!r}; add it to bench/peaks.py") from None
