"""Published peak rates per accelerator chip, keyed by JAX's device_kind.

The yardstick of every roofline and mfu share the benchmark reports.  A
device that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Per-chip peaks.  Rates are per second."""

    bf16_flops: float
    int8_ops: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    # jax.devices()[0].device_kind of a TPU v5e chip
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI"),
}


def peaks(device_kind: str) -> Peaks:
    """The published peaks of `device_kind`; ValueError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak rates for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)} — add a row with its source to "
            "bench/peaks.py") from None
