"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture
table): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per
chip. A device kind that is not in the table is an error, never a
default: a roofline share against another chip's peak means nothing.
"""
from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

PEAKS = {
    "TPU v5 lite": {
        "int8_ops_per_s": 393e12,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak row of ``device_kind``; raises for a kind not listed."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
