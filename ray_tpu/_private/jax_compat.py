"""Instrumented jax entry point: the ledgered batch transfer that feeds
:mod:`ray_tpu.util.device_telemetry` and the step profiler's ``h2d_bytes``.
(The compile tap is jax's own monitoring events, see
``device_telemetry.listen_for_compiles``.)

The package targets the installed jax (0.9.0) and calls ``jax.shard_map``,
``jax.set_mesh``, ``jax.sharding.get_abstract_mesh`` and ``lax.axis_size``
directly; nothing here translates between jax versions.
"""

from __future__ import annotations

import sys

import jax


def _telemetry():
    """The device-telemetry module iff something already imported it —
    the cross-layer probe idiom (train profiler hooks work the same way)
    keeps this module import-free and the no-observer cost at one
    dict miss."""
    return sys.modules.get("ray_tpu.util.device_telemetry")


def device_put_batch(batch, sharding=None, *, transfer_src="device_put_batch"):
    """Transfer a dict-of-columns batch host->device, asynchronously.

    jax.device_put dispatches and returns immediately, so a caller can
    overlap the copy with the step running on the previous batch (the
    ingest double buffer relies on that).  With a ``sharding`` (a
    NamedSharding, e.g. ``parallel.mesh.batch_sharding``) numeric columns
    land already laid out for the step; non-numeric columns (strings,
    objects) stay on host untouched.  A column of lower rank than the
    sharding spec (1-D labels next to 2-D tokens) shards its leading
    axes and replicates the rest — the spec is truncated per column.

    Numeric columns dispatched are ledgered (direction h2d, bytes,
    ``transfer_src``) into the device-telemetry plane when it is loaded —
    probed, not imported, so the no-observer cost is one dict miss — and
    counted into the calling train worker's ``StepProfiler`` row
    (``h2d_bytes``), probed the same way."""
    import numpy as np

    out = {}
    nbytes = 0
    for key, col in batch.items():
        try:
            arr = col if hasattr(col, "dtype") else np.asarray(col)
        except Exception:
            out[key] = col
            continue
        if not hasattr(arr, "dtype") or arr.dtype.kind not in "biufc":
            out[key] = col
            continue
        out[key] = jax.device_put(arr, _fit_sharding(sharding, arr.ndim)) \
            if sharding is not None else jax.device_put(arr)
        nbytes += int(getattr(arr, "nbytes", 0))
    telemetry = _telemetry()
    if telemetry is not None and nbytes:
        telemetry.record_transfer("h2d", nbytes, src=transfer_src)
    profiler = sys.modules.get("ray_tpu.train.profiler")
    if profiler is not None:
        profiler.count("h2d_bytes", nbytes)
    return out


def _fit_sharding(sharding, ndim):
    """Truncate a NamedSharding's PartitionSpec to ``ndim`` axes so one
    batch sharding serves every column rank in a dict batch."""
    spec = getattr(sharding, "spec", None)
    if spec is None or len(spec) <= ndim:
        return sharding
    return jax.sharding.NamedSharding(
        sharding.mesh, jax.sharding.PartitionSpec(*spec[:ndim]))
