"""Published peaks of each accelerator the benchmark accepts, keyed by
JAX's ``device_kind``. The benchmark keeps its own copy so that the
yardstick does not move when the program's hardware table does.

Source of the v5e row: Google Cloud documentation, "TPU v5e" (per chip:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s). No float32
peak is published; the bf16 peak is the ceiling used for every share of
peak compute, and each metric that uses it says so.
"""
from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float  # FLOP/s
    hbm_bytes_per_s: float
    source: str


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9,
        source="Google Cloud TPU v5e documentation: 197 bf16 TFLOP/s, "
               "819 GB/s HBM bandwidth per chip",
    ),
}


def for_kind(device_kind: str) -> Peaks:
    """Peaks of one device kind; an unknown kind is an error, never
    another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
