"""Published peaks per chip, keyed by the ``device_kind`` JAX reports.

A device that is not in the table is an error, never a default: a roofline
share against the wrong chip's peak would be a wrong number, not a
missing one.
"""

from __future__ import annotations

__all__ = ["PEAKS", "UnknownDevice", "peaks_for"]

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


class UnknownDevice(KeyError):
    """The device kind has no row in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peak table for device kind {device_kind!r}; "
            f"have {sorted(PEAKS)}") from None
