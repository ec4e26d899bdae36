"""Accelerator autodetection and the per-chip peaks table — TPU first.

TPU-native analogue of the reference's accelerator plugin registry
(ref: python/ray/_private/accelerators/tpu.py:70 TPUAcceleratorManager), which
detects chips, sets visibility env vars and registers the pod-level
``TPU-<version>-<chips>-head`` resource (tpu.py:356-358) used for gang
scheduling whole slices.  The chip count comes from JAX and nowhere else: one
process per host owns all local chips (README, *Design stance*), so the
process that calls ``ray_tpu.init()`` is the one that initialises the backend.
The ``TPU_*`` variables only name the slice; they are not a count (a one-chip
machine cut from a v5litepod-4 host still exports
``TPU_CHIPS_PER_HOST_BOUNDS=2,2,1``).
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, NamedTuple, Tuple


class DevicePeaks(NamedTuple):
    """Published peaks of one JAX device."""

    #: dense bf16 FLOP/s
    flops: float
    #: HBM bytes/s
    hbm_bw: float


#: Keyed by ``jax.Device.device_kind``.  Source: Google Cloud TPU
#: documentation, system architecture page of each generation.  Only
#: "TPU v5 lite" has been run by this repo (PERF.md, Bring-up).
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v4": DevicePeaks(275e12, 1228e9),
    "TPU v5 lite": DevicePeaks(197e12, 819e9),
    "TPU v5p": DevicePeaks(459e12, 2765e9),
    "TPU v6 lite": DevicePeaks(918e12, 1640e9),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    """Peaks for a ``device_kind``; a device that is not in the table is an
    error, never a default — a utilization against the wrong peak is worse
    than none."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add it to DEVICE_PEAKS in "
            "ray_tpu/_private/accelerators.py with its source") from None


def _tpu_platform_possible() -> bool:
    """False only when JAX was told outright to use platforms other than the
    TPU (``JAX_PLATFORMS=cpu`` in tests, worker nodes and pool workers): then
    there is nothing to ask and no reason to import jax or start a backend."""
    jax = sys.modules.get("jax")
    platforms = (jax.config.jax_platforms if jax is not None
                 else os.environ.get("JAX_PLATFORMS"))
    return not platforms or "tpu" in platforms.lower().split(",")


def _jax_devices() -> List:
    """The local devices as JAX reports them.  Initialises the backend when
    nothing has yet; an initialisation error propagates."""
    import jax

    return jax.local_devices()


def detect_accelerators() -> Tuple[Dict[str, float], Dict[str, str]]:
    """Returns (resources, node labels) for the local host."""
    resources: Dict[str, float] = {}
    labels: Dict[str, str] = {}
    if not _tpu_platform_possible():
        return resources, labels
    tpu_devices = [d for d in _jax_devices() if d.platform == "tpu"]
    if not tpu_devices:
        return resources, labels

    resources["TPU"] = float(len(tpu_devices))
    labels["accelerator-type"] = \
        tpu_devices[0].device_kind.replace(" ", "-").lower()
    # Pod-slice head resource for gang scheduling (ref: tpu.py:356).
    accel_type = os.environ.get("TPU_ACCELERATOR_TYPE", "")
    worker_id = os.environ.get("TPU_WORKER_ID", "0")
    if accel_type and worker_id == "0":
        resources[f"TPU-{accel_type}-head"] = 1.0
    slice_name = os.environ.get("TPU_NAME", "")
    if slice_name:
        labels["ici-slice"] = slice_name
    return resources, labels
