"""Device telemetry plane: XLA compile tracking, HBM pools, transfer ledger.

Every observability layer so far (tracing PR 4, train profiler PR 10, TTFT
attribution PR 12, flight recorder PR 15) measures host-side wall time;
this module watches the XLA/device layer those planes cannot see:

* **Compile tracking** — jax's own monitoring events
  (:func:`listen_for_compiles`: ``backend_compile_duration``, persistent
  cache hits and misses) feed :func:`record_compile`, a per-process
  registry of every executable the process builds or loads, with the label
  the compiling thread set (:class:`compile_label`; ``TrainStep`` and
  ``create_sharded_state`` set one, everything else is ``unlabelled``),
  the abstract shape+sharding signature, seconds, and a classified trigger
  (first_compile / shape_change / sharding_change / donation_change /
  recompile; ``unclassified`` without a signature).  The signature is
  computed only when an event fired, never per step.  Programs that want
  to be found after the run (``TrainStep``: label ``train_step``) register
  themselves with :func:`register_program`; the registry is module state
  and survives ``ray_tpu.shutdown()``.  Rolled
  up cluster-wide through the PR 10 :class:`TimeSeriesCollector` via
  :func:`publish` — N workers compiling the same signature show up as
  duplicated compile-seconds.  A **recompile-storm detector** (recompiles
  per window over threshold) emits an ``xla.compile_storm`` ERROR span and
  a flight-recorder dump, same seam pattern as the hang watchdog's stall
  report; :func:`storm_tick` is driven from ``HangWatchdog.tick``.
* **HBM pool accounting** — named live-byte pools (``kv_blocks``,
  ``mux_weights``, ``ckpt_staging``, ``dag_channel``) tracked host-side
  via :func:`pool_add`/:func:`pool_sub` with high-water marks, plus real
  per-device ``memory_stats()`` when the backend provides them
  (:func:`device_memory_snapshot` — TPU/GPU; the CPU backend usually
  doesn't, so the tracked pools are the fallback truth).
* **Transfer ledger** — every h2d/d2h path calls
  :func:`record_transfer` with direction+bytes+source; windowed
  bandwidth comes from :func:`transfer_bw` (the accessor
  ``ray_tpu.serve.device.transfer_bw`` — same aggregator idiom as the
  serve rollups) and timed transfers land in the Perfetto "device" lane
  as ``device.transfer`` spans.

All hot-path entry points are a few dict ops + a counter inc; spans are
only built when tracing is enabled.  Hook sites reach this module through
``sys.modules.get`` probes (the cross-layer idiom from the train
profiler) so no data/serve/checkpoint layer gains an import dependency.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ray_tpu._private import fault_injection
from ray_tpu.util import flight_recorder, metrics, tracing
from ray_tpu.util.metrics_agent import get_aggregator

#: Compile-record tail retained per process (the full history is in the
#: counters; the tail is what snapshots/bundles embed).
_COMPILE_TAIL = 512
#: Transfer-record tail retained per process.
_TRANSFER_TAIL = 256

#: Recompiles (non-first-compile) inside the window that trip the storm
#: detector.  Env-overridable so chaos tests can trip it deterministically.
DEFAULT_STORM_THRESHOLD = 8
DEFAULT_STORM_WINDOW_S = 60.0

#: Canonical trigger classifications, in precedence order.
TRIGGER_FIRST = "first_compile"
TRIGGER_SHAPE = "shape_change"
TRIGGER_SHARDING = "sharding_change"
TRIGGER_DONATION = "donation_change"
#: Same signature compiled again (cache eviction, duplicated wrapper).
TRIGGER_RECOMPILE = "recompile"
#: An event with no signature to compare (no :class:`compile_label` on the
#: compiling thread, or one without a signature): never a storm's fuel.
TRIGGER_UNCLASSIFIED = "unclassified"

#: jax's monitoring events this module listens to.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
UNLABELLED = "unlabelled"

COMPILES_TOTAL = metrics.Counter(
    "ray_tpu_xla_compiles_total",
    "Executables built or loaded (jax compile events), by the compiling "
    "thread's label and classified trigger.",
    ("label", "trigger"))
COMPILE_SECONDS = metrics.Counter(
    "ray_tpu_xla_compile_seconds_total",
    "Wall seconds spent building or loading executables, by label — summed "
    "across workers via the collector, duplicated signatures show up as "
    "duplicated compile-seconds.",
    ("label",))
COMPILE_STORMS = metrics.Counter(
    "ray_tpu_xla_compile_storms_total",
    "Recompile storms detected (recompiles/window over threshold).")
POOL_BYTES = metrics.Gauge(
    "ray_tpu_device_pool_bytes",
    "Live bytes attributed to a named device-memory pool (kv_blocks, "
    "mux_weights, ckpt_staging, dag_channel).",
    ("pool",))
POOL_PEAK_BYTES = metrics.Gauge(
    "ray_tpu_device_pool_peak_bytes",
    "High-water mark of a named device-memory pool since process start "
    "(or the last reset).",
    ("pool",))
HBM_BYTES = metrics.Gauge(
    "ray_tpu_device_hbm_bytes",
    "Device-reported bytes_in_use per device (memory_stats(); absent on "
    "backends that don't report, e.g. CPU).",
    ("device",))
HBM_PEAK_BYTES = metrics.Gauge(
    "ray_tpu_device_hbm_peak_bytes",
    "Device-reported peak_bytes_in_use per device (memory_stats()).",
    ("device",))
TRANSFER_BYTES = metrics.Counter(
    "ray_tpu_device_transfer_bytes_total",
    "Bytes crossing the host<->device boundary, by direction (h2d/d2h) "
    "and source path (ingest_prefetch, ckpt_snapshot, kv_handoff, "
    "kv_tier, dag_channel, ...).",
    ("direction", "src"))
TRANSFERS_TOTAL = metrics.Counter(
    "ray_tpu_device_transfers_total",
    "Host<->device transfer events, by direction and source path.",
    ("direction", "src"))

_lock = threading.Lock()
#: label -> last-seen signature components, for trigger classification.
_last_sig: Dict[str, Dict[str, Any]] = {}  # guarded_by: _lock
#: Bounded tail of compile records (dicts, JSON-serializable).
_compile_tail: "deque" = deque(maxlen=_COMPILE_TAIL)  # guarded_by: _lock
#: Timestamps of recent non-first compiles, for the storm window.
_recompile_ts: "deque" = deque(maxlen=4096)  # guarded_by: _lock
_storms = 0  # guarded_by: _lock
#: label -> the program object that registered itself (TrainStep).
_programs: Dict[str, Any] = {}  # guarded_by: _lock
#: Bounded tail of first-call records ({"label", "ts", "seconds"}).
_first_calls: "deque" = deque(maxlen=_COMPILE_TAIL)  # guarded_by: _lock
_listening = False  # guarded_by: _lock
#: The compiling thread's :class:`compile_label` and the cache event of the
#: compile in flight on it.
_thread = threading.local()
#: pool -> [live_bytes, peak_bytes]
_pools: Dict[str, List[float]] = {}  # guarded_by: _lock
#: Bounded tail of transfer records.
_transfer_tail: "deque" = deque(maxlen=_TRANSFER_TAIL)  # guarded_by: _lock


# ------------------------------------------------------------------ compiles

def classify_trigger(label: str, shapes: Any, shardings: Any,
                     donation: Any) -> str:
    """What changed vs. the last compile of ``label`` (read-only peek —
    :func:`record_compile` is what updates the last-seen signature)."""
    with _lock:
        prev = _last_sig.get(label)
    return _classify(prev, shapes, shardings, donation)


def _classify(prev: Optional[Dict[str, Any]], shapes: Any, shardings: Any,
              donation: Any) -> str:
    """Pure classification against one previous-signature row (callers
    read ``_last_sig`` under the lock themselves)."""
    if shapes is None:
        return TRIGGER_UNCLASSIFIED
    if prev is None:
        return TRIGGER_FIRST
    if shapes != prev["shapes"]:
        return TRIGGER_SHAPE
    if shardings != prev["shardings"]:
        return TRIGGER_SHARDING
    if donation != prev["donation"]:
        return TRIGGER_DONATION
    return TRIGGER_RECOMPILE


def record_compile(label: str, *, shapes: Any, shardings: Any = None,
                   donation: Any = (), trace_s: float = 0.0,
                   compile_s: float = 0.0, cache: Optional[str] = None,
                   ts: Optional[float] = None) -> str:
    """Record one executable built or loaded; returns the classified
    trigger.  ``shapes``/``shardings``/``donation`` are opaque hashable
    signature components — classification only compares them against the
    label's previous compile; ``shapes=None`` is an event nobody could
    sign (``unclassified``).  ``cache`` is the persistent cache's answer
    for this compile ("hit" / "miss" / None when it was not asked)."""
    t = time.time() if ts is None else ts
    with _lock:
        trigger = _classify(_last_sig.get(label), shapes, shardings,
                            donation)
        if shapes is not None:
            _last_sig[label] = {"shapes": shapes, "shardings": shardings,
                                "donation": donation}
        _compile_tail.append({
            "label": label, "trigger": trigger, "ts": t,
            "trace_s": round(float(trace_s), 6),
            "compile_s": round(float(compile_s), 6),
            "cache": cache,
            "signature": repr(shapes)[:200],
        })
        recompiled = trigger not in (TRIGGER_FIRST, TRIGGER_UNCLASSIFIED)
        if recompiled:
            _recompile_ts.append(t)
    COMPILES_TOTAL.inc(tags={"label": label, "trigger": trigger})
    COMPILE_SECONDS.inc(trace_s + compile_s, tags={"label": label})
    wall = trace_s + compile_s
    tracing.record_span("xla.compile", t - wall, t,
                        attributes={"label": label, "trigger": trigger,
                                    "trace_s": trace_s,
                                    "compile_s": compile_s})
    if recompiled:
        storm_tick(now=t)
    return trigger


class compile_label:
    """``with compile_label("train_step", signature):`` — compile events
    jax fires on this thread inside the block are recorded under the
    label.  ``signature`` is a zero-argument callable returning
    ``(shapes, shardings, donation)``; it runs only when an event fired,
    so a steady step pays two thread-local stores and nothing else.
    ``compiles`` / ``compile_s`` say afterwards what the block built."""

    __slots__ = ("label", "signature", "compiles", "compile_s", "_outer")

    def __init__(self, label: str, signature=None):
        self.label = label
        self.signature = signature
        self.compiles = 0
        self.compile_s = 0.0

    def __enter__(self):
        self._outer = getattr(_thread, "label", None)
        _thread.label = self
        return self

    def __exit__(self, et, ev, tb):
        _thread.label = self._outer
        return False


def listen_for_compiles() -> None:
    """Register this module with jax's monitoring events, once per
    process.  Callers have imported jax already (``TrainStep``,
    ``create_sharded_state``): the telemetry plane never imports it."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    from jax import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def _on_event(event: str, **_: Any) -> None:
    # Fires inside the compile, before its duration event, same thread.
    answer = _CACHE_EVENTS.get(event)
    if answer is not None:
        _thread.cache = answer


def _on_duration(event: str, seconds: float, **_: Any) -> None:
    if event != _COMPILE_EVENT:
        return
    cache, _thread.cache = getattr(_thread, "cache", None), None
    ctx = getattr(_thread, "label", None)
    shapes, shardings, donation = None, None, ()
    if ctx is not None:
        ctx.compiles += 1
        ctx.compile_s += seconds
        if ctx.signature is not None:
            shapes, shardings, donation = ctx.signature()
    record_compile(UNLABELLED if ctx is None else ctx.label, shapes=shapes,
                   shardings=shardings, donation=donation,
                   compile_s=seconds, cache=cache)
    # The step profiler's row counts what its own thread compiled (probed:
    # no profiler module, no train worker in the process).
    profiler = sys.modules.get("ray_tpu.train.profiler")
    if profiler is not None:
        profiler.count("compiles", 1)
        profiler.count("compile_s", seconds)


def register_program(label: str, program: Any) -> None:
    """Keep ``program`` findable under ``label`` after the run that built
    it (the newest wins): readers reach ``TrainStep.anatomy()`` through
    :func:`program` once ``fit()`` has returned."""
    with _lock:
        _programs[label] = program


def program(label: str) -> Optional[Any]:
    with _lock:
        return _programs.get(label)


def record_first_call(label: str, seconds: float,
                      ts: Optional[float] = None, **attributes: Any) -> None:
    """One call of a labelled program that built or loaded its executable
    (trace, lower, compile or cache load, dispatch), ``ts`` its end;
    ``attributes`` are the caller's own (``TrainStep``: what the layers keep
    for the backward, ``remat_*``)."""
    row = {"label": label, "ts": time.time() if ts is None else ts,
           "seconds": round(float(seconds), 6), **attributes}
    with _lock:
        _first_calls.append(row)


def first_calls(label: Optional[str] = None) -> List[dict]:
    """Retained first-call records (optionally one label's), oldest
    first."""
    with _lock:
        rows = list(_first_calls)
    if label is not None:
        rows = [r for r in rows if r["label"] == label]
    return rows


def compile_records(label: Optional[str] = None) -> List[dict]:
    """Retained compile-record tail (optionally one label's), oldest
    first."""
    with _lock:
        rows = list(_compile_tail)
    if label is not None:
        rows = [r for r in rows if r["label"] == label]
    return rows


def compile_totals() -> Dict[str, Any]:
    """{"compiles", "compile_seconds", "by_trigger", "storms"} summed over
    the retained tail (tests and snapshots; the counters hold lifetime
    totals)."""
    with _lock:
        rows = list(_compile_tail)
        storms = _storms
    by_trigger: Dict[str, int] = {}
    for r in rows:
        by_trigger[r["trigger"]] = by_trigger.get(r["trigger"], 0) + 1
    return {"compiles": len(rows),
            "compile_seconds": round(
                sum(r["trace_s"] + r["compile_s"] for r in rows), 6),
            "by_trigger": by_trigger,
            "storms": storms}


def storm_tick(now: Optional[float] = None) -> bool:
    """One storm-detection pass (called inline after every recompile and
    from ``HangWatchdog.tick`` via a module probe): True when recompiles
    inside the window crossed the threshold.  Firing drains the window so
    the detector re-arms only after a fresh burst — a sustained storm
    reports once per threshold-worth of recompiles, not per tick."""
    t = time.time() if now is None else now
    threshold = int(os.environ.get("RAY_TPU_COMPILE_STORM_THRESHOLD",
                                   DEFAULT_STORM_THRESHOLD))
    window_s = float(os.environ.get("RAY_TPU_COMPILE_STORM_WINDOW_S",
                                    DEFAULT_STORM_WINDOW_S))
    with _lock:
        while _recompile_ts and _recompile_ts[0] < t - window_s:
            _recompile_ts.popleft()
        if threshold <= 0 or len(_recompile_ts) < threshold:
            return False
        since = _recompile_ts[0]
        count = len(_recompile_ts)
        _recompile_ts.clear()
        global _storms
        _storms += 1
    _report_storm(since, t, count, threshold, window_s)
    return True


def _report_storm(since: float, detected: float, count: int,
                  threshold: int, window_s: float) -> None:
    """Same seam pattern as the watchdog's stall report: metrics + a ring
    event + a retroactive ERROR span + a postmortem dump, all best-effort
    — forensics must never worsen the storm being recorded."""
    COMPILE_STORMS.inc()
    detail = {"recompiles": count, "threshold": threshold,
              "window_s": window_s, "since": since}
    rec = flight_recorder.get_recorder()
    if rec is not None:
        try:
            rec.record_event("xla.compile_storm", detail, now=detected,
                             kind="storm", status="ERROR")
        except Exception:
            pass
    tracing.record_span("xla.compile_storm", since, detected,
                        attributes=detail, status="ERROR: CompileStorm")
    flight_recorder.trigger_dump("compile_storm", detail)


# --------------------------------------------------------------------- pools

def pool_add(pool: str, nbytes: float) -> None:
    """Attribute ``nbytes`` more live bytes to a named pool."""
    _pool_delta(pool, float(nbytes))


def pool_sub(pool: str, nbytes: float) -> None:
    """Release ``nbytes`` from a named pool (floored at zero — release
    paths may run on state an earlier failure already partially freed)."""
    _pool_delta(pool, -float(nbytes))


def _pool_delta(pool: str, delta: float) -> None:
    with _lock:
        row = _pools.get(pool)
        if row is None:
            row = _pools[pool] = [0.0, 0.0]
        row[0] = max(0.0, row[0] + delta)
        row[1] = max(row[1], row[0])
        cur, peak = row
    POOL_BYTES.set(cur, tags={"pool": pool})
    POOL_PEAK_BYTES.set(peak, tags={"pool": pool})


def pool_set(pool: str, nbytes: float) -> None:
    """Set a pool's live bytes absolutely (rebuild-from-scratch callers)."""
    with _lock:
        row = _pools.get(pool)
        if row is None:
            row = _pools[pool] = [0.0, 0.0]
        row[0] = max(0.0, float(nbytes))
        row[1] = max(row[1], row[0])
        cur, peak = row
    POOL_BYTES.set(cur, tags={"pool": pool})
    POOL_PEAK_BYTES.set(peak, tags={"pool": pool})


def pool_bytes() -> Dict[str, Dict[str, float]]:
    """{pool: {"bytes": live, "peak": high-water}} for every tracked pool."""
    with _lock:
        return {p: {"bytes": row[0], "peak": row[1]}
                for p, row in _pools.items()}


def device_memory_snapshot() -> List[Dict[str, Any]]:
    """Per-device ``memory_stats()`` rows where the backend reports them
    (TPU/GPU); devices without stats (CPU) are skipped — the tracked
    pools above are the host-side fallback.  Updates the HBM gauges."""
    rows: List[Dict[str, Any]] = []
    try:
        import jax

        devices = jax.devices()
    except Exception:
        return rows
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            continue
        if not stats:
            continue
        dev = str(d.id)
        in_use = float(stats.get("bytes_in_use", 0.0))
        peak = float(stats.get("peak_bytes_in_use", in_use))
        rows.append({"device": dev,
                     "platform": getattr(d, "platform", "unknown"),
                     "bytes_in_use": in_use, "peak_bytes_in_use": peak,
                     "bytes_limit": float(stats.get("bytes_limit", 0.0))})
        HBM_BYTES.set(in_use, tags={"device": dev})
        HBM_PEAK_BYTES.set(peak, tags={"device": dev})
    return rows


def tree_nbytes(tree: Any) -> int:
    """Best-effort payload bytes of a nested list/tuple/dict of array
    leaves (trusts real ``nbytes``, including 0; leaves without one count
    0 — toy-payload tests keep working, numpy/jax arrays are exact)."""
    total = 0
    stack = [tree]
    while stack:
        obj = stack.pop()
        nbytes = getattr(obj, "nbytes", None)
        if nbytes is not None:
            try:
                total += int(nbytes)
            except Exception:
                pass
            continue
        if isinstance(obj, dict):
            stack.extend(obj.values())
        elif isinstance(obj, (list, tuple)):
            stack.extend(obj)
    return total


# ------------------------------------------------------------------ transfers

def record_transfer(direction: str, nbytes: float, *, src: str = "",
                    start: Optional[float] = None,
                    end: Optional[float] = None) -> None:
    """Ledger one host<->device transfer (``direction`` is "h2d"/"d2h").
    When ``start``/``end`` are given the transfer also lands in the
    Perfetto device lane as a ``device.transfer`` span."""
    t = time.time() if end is None else end
    tags = {"direction": direction, "src": src}
    TRANSFER_BYTES.inc(max(0.0, float(nbytes)), tags=tags)
    TRANSFERS_TOTAL.inc(tags=tags)
    with _lock:
        _transfer_tail.append({"ts": t, "direction": direction, "src": src,
                               "bytes": int(nbytes)})
    if start is not None and tracing.is_tracing_enabled():
        tracing.record_span("device.transfer", start, t,
                            attributes={"direction": direction, "src": src,
                                        "bytes": int(nbytes)})


def transfer_records() -> List[dict]:
    """Retained transfer-ledger tail, oldest first."""
    with _lock:
        return list(_transfer_tail)


def transfer_bw(direction: Optional[str] = None, *, src: Optional[str] = None,
                window_s: float = 60.0,
                now: Optional[float] = None) -> float:
    """Windowed host<->device bandwidth (bytes/s) over the trailing
    window, optionally filtered by direction and/or source path — the
    same sample-then-query aggregator idiom as the serve accessors."""
    agg = get_aggregator()
    agg.sample_registry(ts=now)
    tags: Dict[str, str] = {}
    if direction is not None:
        tags["direction"] = direction
    if src is not None:
        tags["src"] = src
    return agg.window_rate("ray_tpu_device_transfer_bytes_total",
                           tags or None, window_s, now)


# ------------------------------------------------------------------- snapshot

def snapshot(*, transfer_window_s: float = 60.0,
             now: Optional[float] = None) -> Dict[str, Any]:
    """JSON-serializable device-telemetry snapshot: compile registry tail
    + totals, pool high-water, transfer window + tail, device memory.
    What forensics bundles embed and ``serve.status()`` / the train run
    registry surface.  Consults the ``device_telemetry_snapshot`` fault
    point — chaos proves every embedding site absorbs a telemetry
    failure."""
    fault_injection.check("device_telemetry_snapshot")
    t = time.time() if now is None else now
    totals = compile_totals()
    return {
        "ts": t,
        "compiles": {
            "totals": totals,
            "tail": compile_records()[-50:],
            "first_calls": first_calls()[-50:],
        },
        "pools": pool_bytes(),
        "transfers": {
            "tail": transfer_records()[-50:],
            "window_s": transfer_window_s,
            "bytes_per_s": {
                "h2d": transfer_bw("h2d", window_s=transfer_window_s,
                                   now=now),
                "d2h": transfer_bw("d2h", window_s=transfer_window_s,
                                   now=now),
            },
        },
        "device_memory": device_memory_snapshot(),
    }


def publish(collector: Any, source: str = "", *,
            since: Optional[float] = None,
            now: Optional[float] = None) -> Any:
    """Roll this process's metric window up to a
    :class:`~ray_tpu.util.metrics_agent.TimeSeriesCollector` (plain
    instance or named actor handle): sample the registry, snapshot the
    aggregator, push tagged with ``source`` so per-worker compile-seconds
    stay distinct series that cluster queries sum."""
    agg = get_aggregator()
    agg.sample_registry(ts=now)
    snap = agg.snapshot(since=since)
    push = collector.push
    if hasattr(push, "remote"):  # actor handle
        return push.remote(snap, source)
    return push(snap, source)


def reset() -> None:
    """Drop all retained state (tests / bench arms): compile registry with
    its programs and first calls, storm window, pools (gauges cleared),
    transfer tail.  The jax listeners stay registered."""
    with _lock:
        _last_sig.clear()
        _compile_tail.clear()
        _recompile_ts.clear()
        _programs.clear()
        _first_calls.clear()
        _transfer_tail.clear()
        _pools.clear()
        global _storms
        _storms = 0
    POOL_BYTES.clear()
    POOL_PEAK_BYTES.clear()
    HBM_BYTES.clear()
    HBM_PEAK_BYTES.clear()
