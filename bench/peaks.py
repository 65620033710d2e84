"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
A device kind that is not listed is an error, never a default.
"""
from __future__ import annotations

__all__ = ["PEAKS", "peaks"]

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12,
        "int8_op_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind that
    has no published entry here."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
