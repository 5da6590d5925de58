"""Peak rates of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture, chip
specifications): 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at
819 GB/s, 1,600 Gbit/s of inter-chip interconnect. A device that is not
in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {kind!r}: add it to "
                       "benchmarks/chip/peaks.py with its source") from None


def least_time(flops: float, nbytes: float, kind: str) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    p = peaks(kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
