"""Peak rates of each chip, keyed by the ``device_kind`` JAX reports.

A kind missing from the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flop_s": 197e12, "int8_op_s": 393e12,
                    "hbm_byte_s": 819e9, "hbm_bytes": 16e9},
}


class UnknownDevice(KeyError):
    pass


def peaks_for(kind: str) -> Dict[str, float]:
    if kind not in PEAKS:
        raise UnknownDevice(f"device_kind {kind!r} is not in the peaks "
                            f"table (known: {sorted(PEAKS)})")
    return PEAKS[kind]
