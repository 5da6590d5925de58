"""Per-chip peak rates: one table, keyed by ``jax.Device.device_kind``.

Every roofline term in the repo (probe estimates, the dry-run roofline, the
synthetic gang workloads, the topology's link model) reads its peaks here.

Source of the v5e entry: Google Cloud documentation, "TPU v5e" (system
architecture, chip specifications): 197 TFLOP/s bf16, 16 GB of HBM2 at
819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip — four ICI
links, so 50 GB/s per link.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    kind: str            # jax.Device.device_kind
    flops: float         # bf16 FLOP/s per chip
    hbm_bw: float        # HBM bytes/s per chip
    ici_bw: float        # bytes/s per ICI link
    hbm_bytes: int       # published HBM capacity (the usable limit is the
    #                      device's memory_stats()["bytes_limit"])


V5E = ChipPeaks(kind="TPU v5 lite", flops=197e12, hbm_bw=819e9,
                ici_bw=50e9, hbm_bytes=16 * 10**9)

PEAKS = {
    V5E.kind: V5E,
    # The CPU backend (tests, the simulator's studies, rehearsals here) has
    # no roofline worth scheduling against: its probes are estimated as if
    # for the v5e the kernels are written for. An explicit entry, so an
    # unknown accelerator still fails instead of inheriting these numbers.
    "cpu": V5E,
}


def peaks_for(kind: str) -> ChipPeaks:
    """Peaks of the chip named ``kind``; an unknown kind raises."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {kind!r}: add it to "
                       f"repro.core.chips.PEAKS with its source") from None


def device_peaks(device=None) -> ChipPeaks:
    """Peaks of ``device`` (default: the first attached device). Any CPU
    device maps to the ``"cpu"`` entry whatever its ``device_kind``."""
    if device is None:
        import jax
        device = jax.devices()[0]
    return peaks_for("cpu" if device.platform == "cpu"
                     else device.device_kind)
