"""Published peak rates of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(
        flops_bf16=197e12,  # FLOP/s
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, TPU v5e",
    ),
}


def peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind``; raises KeyError for an unknown device."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
