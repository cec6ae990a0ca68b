"""Published peaks of the accelerators the benchmark runs on, keyed by
``jax.Device.device_kind``.  A kind missing here is an error: a share of
some other chip's peak would mean nothing.

TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect per chip).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak_for(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise SystemExit(f"no peak for device kind {kind!r}; the table "
                         f"knows {sorted(PEAKS)}") from None
