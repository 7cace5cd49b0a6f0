"""Per-chip hardware peaks, keyed by ``jax.Device.device_kind``.

Source for "TPU v5 lite" (the ``device_kind`` JAX reports for a TPU
v5e chip): Google Cloud documentation, "TPU v5e" -- 197 TFLOP/s bf16,
16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect
(200 GB/s, over four ICI links of 50 GB/s each).

A device kind missing from :data:`CHIPS` is an error, never a default:
add it here with its published source.
"""

from __future__ import annotations

from typing import NamedTuple


class ChipPeaks(NamedTuple):
    peak_flops: float        # bf16 FLOP/s
    hbm_bw: float            # bytes/s
    ici_bw: float            # bytes/s per link
    hbm_bytes: float


CHIPS = {
    "TPU v5 lite": ChipPeaks(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                             hbm_bytes=16e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Published peaks of ``device_kind``; ``KeyError`` if unknown."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(CHIPS)}") from None
