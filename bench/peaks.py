"""Published peaks of each chip, keyed by JAX's ``device_kind``.

TPU v5e: 197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth (Google Cloud
documentation, "TPU v5e"); the same numbers as
``repro.launch.roofline.V5E``.  A kind that is not here is an error.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_per_s: float      # bf16 matrix units
    hbm_bytes_per_s: float


PEAKS = {
    "TPU v5 lite": Peak(flops_per_s=197e12, hbm_bytes_per_s=819e9),
    "TPU v5e": Peak(flops_per_s=197e12, hbm_bytes_per_s=819e9),
}


def for_device(kind: str) -> Peak:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return PEAKS[kind]
