"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A device that is not in the table is an error, never a default: a share of
a peak that silently used the wrong chip's numbers would be meaningless.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add it to {__name__}.PEAKS "
                         f"with its source") from None
