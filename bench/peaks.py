"""Published peak rates per chip, keyed by ``jax.Device.device_kind``.

A device that is not in the table is an error, not a default: a share
of some other chip's peak is no share at all.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB of HBM at 819 GB/s per chip.
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9,
                    "source": 'Google Cloud, "TPU v5e"'},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
