"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s. A device that
is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"bench: no published peaks for device kind {device_kind!r}; "
            f"add them to bench/peaks.py with their source") from None
