"""Published per-chip facts, keyed by ``jax.Device.device_kind``.

The kernel dispatch rule (``kernels/dispatch.py``) reads the scoped-VMEM
limit from here; the roofline analysis (``launch/roofline.py``) and the
benchmark's bandwidth fractions read the peaks.  A kind missing from
:data:`PEAKS` is an error, never a guess.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one device kind."""
    bf16_flops: float        # FLOP/s
    hbm_bw: float            # bytes/s
    hbm_bytes: float         # bytes
    ici_bw: float            # bytes/s per link, per direction
    scoped_vmem_bytes: int   # Mosaic's default scoped-VMEM limit per kernel


#: Source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip over
#: 4 links (50 GB/s per link and direction).  The scoped-VMEM limit is the
#: one the TPU compiler applies to a Pallas kernel that sets no
#: ``vmem_limit_bytes`` on v5e (16 MiB).
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bw=819e9,
                             hbm_bytes=16e9, ici_bw=50e9,
                             scoped_vmem_bytes=16 * 2**20),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; an unknown kind raises ``KeyError``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
