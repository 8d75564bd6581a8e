"""Published peaks per chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        )
    return PEAKS[device_kind]


RATES = ("bf16_flops", "int8_ops", "hbm_bytes_per_s")


def deployment(peak: dict, chips: int) -> dict:
    """The peaks of `chips` chips serving together: a copy of one chip's
    with each rate times `chips`; `hbm_bytes` stays one chip's."""
    return dict(peak, **{k: peak[k] * chips for k in RATES})
