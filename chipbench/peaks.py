"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bfloat16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s per chip.  A device kind that
is not in the table is an error: no roofline share is ever computed against
another chip's peaks.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
