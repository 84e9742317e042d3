"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.

A device that is not in the table is an error, never a default: a share of
a peak computed against the wrong chip is not a measurement.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes_per_s: float   # bytes/s
    hbm_bytes: float         # bytes of device memory
    source: str


TABLE = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12,
                         hbm_bytes_per_s=819e9, hbm_bytes=16e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def for_kind(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(TABLE)}") from None
