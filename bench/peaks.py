"""Peak rates of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A kind that is not listed is an error:
no chip inherits another's peaks.

Source of the v5e row: Google Cloud documentation, "TPU v5e" (per chip:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The row of ``device_kind``; raises ``KeyError`` for a kind that has
    no published peaks here."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
