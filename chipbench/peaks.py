"""Published peaks of each chip, keyed by JAX's ``device_kind``.

A copy of the program's table (``repro.perf.roofline.PEAKS``), kept with
the benchmark so that no change to the program moves the yardstick.  A
kind that is not here is an error, never a default.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class Peaks:
    flops: float       # FLOP/s of the matrix unit in bfloat16
    hbm_bw: float      # bytes/s of device memory
    source: str


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" add them with their source to chipbench/peaks.py")
    return PEAKS[device_kind]
