"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at
819 GB/s.  A device that is not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16e9,
        hbm_bytes_per_s=819e9,
        source="Google Cloud documentation, 'TPU v5e'"),
}


def lookup(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
