"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per-chip specifications):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
chip-to-chip interconnect.  A kind that is not here is an error, never a
default.
"""
from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,     # FLOP/s
        "hbm_bw": 819e9,          # bytes/s
        "hbm_bytes": 16e9,        # bytes
        "ici_bw": 1600e9 / 8,     # bytes/s per chip
    },
}


def peaks(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
