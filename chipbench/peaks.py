"""Published peaks of the chips the benchmark knows, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page): one
chip does 197 TFLOP/s in bf16 and 393 TOP/s in int8 and has 16 GB of HBM at
819 GB/s. A device kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    """The row of ``device_kind``; ``KeyError`` with the known kinds otherwise."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)}); add its row with a source")
    return PEAKS[device_kind]
