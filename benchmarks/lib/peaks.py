"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

The benchmark's own copy: a later PR may change the program's table
(``ray_tpu/_private/accelerators.py``), never the yardstick.  A device that is
not here is an error, not a default.
"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    #: dense bf16 FLOP/s of one chip
    flops: float
    #: HBM bytes/s of one chip
    hbm_bw: float
    #: HBM bytes of one chip
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        197e12, 819e9, 16e9,
        "Google Cloud TPU documentation, 'TPU v5e' system architecture: 197 "
        "TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmarks/lib/peaks.py (known: {sorted(PEAKS)}); add a row "
            "with its source") from None
