"""Published peak rates, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect per chip.

A float32 matrix product at the TPU's default precision runs as one bf16
pass, so the bf16 peak is the ceiling for the programs measured here.  A
device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,            # bf16 FLOP/s
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,   # 1,600 Gbit/s
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add them, with their source, to bench/harness/peaks.py")
    return PEAKS[device_kind]
