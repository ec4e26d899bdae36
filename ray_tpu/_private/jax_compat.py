"""Instrumented jax entry points: the compile tap and the ledgered batch
transfer that feed :mod:`ray_tpu.util.device_telemetry`.

The package targets the installed jax (0.9.0) and calls ``jax.shard_map``,
``jax.set_mesh``, ``jax.sharding.get_abstract_mesh`` and ``lax.axis_size``
directly; nothing here translates between jax versions.
"""

from __future__ import annotations

import sys
import time

import jax


def _telemetry():
    """The device-telemetry module iff something already imported it —
    the cross-layer probe idiom (train profiler hooks work the same way)
    keeps this module import-free and the no-observer cost at one
    dict miss."""
    return sys.modules.get("ray_tpu.util.device_telemetry")


class InstrumentedJit:
    """``jax.jit`` with a compile tap: every trace/lower/compile is timed
    and recorded into :mod:`ray_tpu.util.device_telemetry` with a
    classified trigger (first_compile / shape_change / sharding_change /
    donation_change).

    Uses the AOT path — ``jitted.lower(*args)`` (trace+lower wall) then
    ``.compile()`` (compile wall) — cached per abstract signature, so the
    steady-state call is one tuple-build + dict hit + compiled dispatch
    (the bench_profiler A/B gates this at <=1% of a GPT-2 train step).
    Positional args only, matching how the repo calls its jitted steps.
    """

    def __init__(self, fn, *, label=None, donate_argnums=(), **jit_kwargs):
        self._jitted = jax.jit(fn, donate_argnums=donate_argnums,
                               **jit_kwargs)
        self.label = label or getattr(fn, "__name__", "jit_fn")
        self._donation = tuple(donate_argnums) if donate_argnums else ()
        self._cache = {}

    @staticmethod
    def _signature(args):
        """(shapes, shardings) abstract signature of positional args:
        array leaves key by shape+dtype (+ the pytree structure), python
        scalars by type (jit traces them — a changed value is not a
        changed signature), shardings by the sharding objects themselves
        (hashable, equality = same committed placement).  Raw objects,
        not reprs — repr of a sharding walks its device list and would
        dominate the steady-state dispatch the bench gates at <=1%."""
        leaves, treedef = jax.tree_util.tree_flatten(args)
        shapes = []
        shardings = []
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                shapes.append(type(leaf).__name__)
                shardings.append(None)
            else:
                shapes.append((tuple(shape), dtype))
                shardings.append(getattr(leaf, "sharding", None))
        return (tuple(shapes), treedef), tuple(shardings)

    def __call__(self, *args):
        shapes, shardings = self._signature(args)
        key = (shapes, shardings)
        compiled = self._cache.get(key)
        if compiled is None:
            t0 = time.perf_counter()
            lowered = self._jitted.lower(*args)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
            self._cache[key] = compiled
            from ray_tpu.util import device_telemetry

            device_telemetry.record_compile(
                self.label, shapes=shapes, shardings=shardings,
                donation=self._donation, trace_s=t1 - t0,
                compile_s=t2 - t1)
        return compiled(*args)


def instrumented_jit(fn, *, label=None, donate_argnums=(), **jit_kwargs):
    """Drop-in for ``jax.jit(fn, donate_argnums=...)`` that records every
    compile into the device-telemetry plane (see :class:`InstrumentedJit`)."""
    return InstrumentedJit(fn, label=label, donate_argnums=donate_argnums,
                           **jit_kwargs)


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
    probed, not imported, so the no-observer cost is one dict miss."""
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
    return out


def _fit_sharding(sharding, ndim):
    """Truncate a NamedSharding's PartitionSpec to ``ndim`` axes so one
    batch sharding serves every column rank in a dict batch."""
    spec = getattr(sharding, "spec", None)
    if spec is None or len(spec) <= ndim:
        return sharding
    return jax.sharding.NamedSharding(
        sharding.mesh, jax.sharding.PartitionSpec(*spec[:ndim]))
