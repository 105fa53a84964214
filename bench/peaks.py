"""Published peaks of each chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of `device_kind`; a device not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/peaks.py with their source") from None
