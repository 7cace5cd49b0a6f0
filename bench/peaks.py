"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A copy of the program's table (``repro.roofline.constants``), kept with
the benchmark so that no change to the program moves a yardstick.

Source for "TPU v5 lite" (the kind JAX reports for a TPU v5e chip):
Google Cloud documentation, "TPU v5e" -- 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect (four ICI links of 50 GB/s).  No peak of the vector unit
(VPU) is published, so no roofline of an elementwise kernel is taken
against this table.

A device kind missing from :data:`CHIPS` is an error, never a default.
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
