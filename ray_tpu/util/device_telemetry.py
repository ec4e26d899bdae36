"""Device telemetry plane: XLA compile tracking, HBM pools, transfer ledger.

Every observability layer so far (tracing PR 4, train profiler PR 10, TTFT
attribution PR 12, flight recorder PR 15) measures host-side wall time;
this module watches the XLA/device layer those planes cannot see:

* **Compile tracking** — jax's own monitoring events
  (:func:`listen_for_compiles`: the trace, the lowering and
  ``backend_compile_duration`` as time spans, the persistent cache's hits,
  misses and retrieval seconds) feed :func:`record_compile`, a per-process
  registry of every executable the process builds or loads, with the label
  the compiling thread set (:class:`compile_label`; ``TrainStep`` and
  ``create_sharded_state`` set one, everything else is ``unlabelled``),
  the abstract shape+sharding signature, the seconds of each phase (trace,
  lower, compile, and of the compile the cache's load), and a classified trigger
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
* **The set-up's account** — :func:`setup_account`: from the process's
  start to the end of the first steady step, the spans of the set-up
  (:class:`setup_span`, :func:`record_setup_span`) in order, what they
  cover and the gaps between them; always on, a handful of rows a process.
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

import logging
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import fault_injection
from ray_tpu.util import flight_recorder, metrics, tracing
from ray_tpu.util.metrics_agent import get_aggregator

#: Compile-record tail retained per process (the full history is in the
#: counters; the tail is what snapshots/bundles embed).
_COMPILE_TAIL = 512
#: Transfer-record tail retained per process.
_TRANSFER_TAIL = 256
#: Rows the set-up's account takes before it closes (a run has about eight).
_ACCOUNT_ROWS = 128
#: What a row of the account keeps of its span's attributes.
_ACCOUNT_KEYS = ("label", "trace_s", "lower_s", "compile_s", "cache_load_s",
                 "cache", "other_s", "workers", "worker_mode", "bytes")

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
#: The time spans of a compile's phases -> the :class:`compile_label`
#: attribute that sums the phase.  The compile's own span sums nowhere
#: (``compile_s`` is its duration event, as ever); it is here so that a trace
#: it ran inside does not count it again.
_PHASE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
                 _COMPILE_EVENT: None}
#: Duration events jax fires inside a compile the persistent cache answered
#: -> where the thread keeps them until the compile's own event.
_CACHE_DURATIONS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}
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
TIME_TO_FIRST_STEP = metrics.Gauge(
    "ray_tpu_train_time_to_first_step_seconds",
    "Seconds from the process's start to the end, on the device, of the "
    "first train step whose call compiled nothing (setup_account(); set "
    "once, when the account closes).")
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
#: The set-up's account: its rows while it is open, then how it closed.
_account_rows: List[dict] = []  # guarded_by: _lock
_account_closed: Optional[Dict[str, Any]] = None  # guarded_by: _lock
#: (time.time() at the process's start, where that was read)
_process_started: Optional[Tuple[float, str]] = None  # guarded_by: _lock
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
                   lower_s: float = 0.0, compile_s: float = 0.0,
                   cache: Optional[str] = None, cache_load_s: float = 0.0,
                   saved_s: float = 0.0, ts: Optional[float] = None,
                   start: Optional[float] = None) -> str:
    """Record one executable built or loaded; returns the classified
    trigger.  ``shapes``/``shardings``/``donation`` are opaque hashable
    signature components — classification only compares them against the
    label's previous compile; ``shapes=None`` is an event nobody could
    sign (``unclassified``).  ``trace_s``, ``lower_s`` and ``compile_s``
    are the seconds of its three phases; ``cache`` is the persistent cache's
    answer for this compile ("hit" / "miss" / None when it was not asked),
    ``cache_load_s`` the part of ``compile_s`` that read the entry and
    ``saved_s`` what jax says the hit saved.  ``ts`` is the end and
    ``start`` the start of the trace (else the end less the phases)."""
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
            "lower_s": round(float(lower_s), 6),
            "compile_s": round(float(compile_s), 6),
            "cache": cache,
            "cache_load_s": round(float(cache_load_s), 6),
            "saved_s": round(float(saved_s), 6),
            "signature": repr(shapes)[:200],
        })
        recompiled = trigger not in (TRIGGER_FIRST, TRIGGER_UNCLASSIFIED)
        if recompiled:
            _recompile_ts.append(t)
    wall = trace_s + lower_s + compile_s
    COMPILES_TOTAL.inc(tags={"label": label, "trigger": trigger})
    COMPILE_SECONDS.inc(wall, tags={"label": label})
    tracing.record_span("xla.compile", t - wall if start is None else start,
                        t, attributes={"label": label, "trigger": trigger,
                                       "trace_s": trace_s,
                                       "lower_s": lower_s,
                                       "compile_s": compile_s,
                                       "cache": cache,
                                       "cache_load_s": cache_load_s})
    if recompiled:
        storm_tick(now=t)
    return trigger


class compile_label:
    """``with compile_label("train_step", signature):`` — compile events
    jax fires on this thread inside the block are recorded under the
    label.  ``signature`` is a zero-argument callable returning
    ``(shapes, shardings, donation)``; it runs only when an event fired,
    so a steady step pays two thread-local stores and nothing else.

    Afterwards the label says what the block built and where the seconds
    went: ``compiles`` / ``compile_s`` (jax's ``backend_compile_duration``,
    a cache load included), ``trace_s`` and ``lower_s`` (the time spans of
    the trace and of the lowering to MLIR), ``cache_load_s`` and ``saved_s``
    (of the compiles the persistent cache answered: the read, and what jax
    says it saved), ``hits`` / ``misses``.  Nested jits fire a trace span
    each, innermost first, each inside its caller's: a span counts for its
    own phase less every span it holds, so the three phases sum to the
    union of the intervals and nothing is counted twice.  A label is for
    one block (``TrainStep`` makes one a call): the spans it still holds
    are those no later one has enclosed."""

    # What a block that compiles nothing never writes stays the class's: a
    # steady step's label costs its two arguments and nothing else.
    compiles = hits = misses = 0
    compile_s = trace_s = lower_s = cache_load_s = saved_s = 0.0
    #: (start, seconds) of the spans no later span has enclosed, in order
    _spans: Optional[List[Tuple[float, float]]] = None
    #: (trace_s, lower_s) already given to a compile record
    _filed = (0.0, 0.0)
    #: the earliest start among the spans since that record
    _since: Optional[float] = None

    def __init__(self, label: str, signature=None):
        self.label = label
        self.signature = signature

    def __enter__(self):
        self._outer = getattr(_thread, "label", None)
        _thread.label = self
        return self

    def __exit__(self, et, ev, tb):
        _thread.label = self._outer
        return False

    def _span(self, phase: Optional[str], start: float, end: float) -> None:
        """One time span of a phase.  Spans on one thread nest or lie apart
        and arrive by their ends, so those this one holds are the last of
        ``_spans``: constant work a span, however deep the jits nest."""
        own = end - start
        spans = self._spans
        if spans is None:
            spans = self._spans = []
        while spans and spans[-1][0] >= start:
            own -= spans.pop()[1]
        spans.append((start, end - start))
        if self._since is None or start < self._since:
            self._since = start
        if phase is not None:
            setattr(self, phase, getattr(self, phase) + max(own, 0.0))

    def _file(self) -> Tuple[float, float, Optional[float]]:
        """(trace_s, lower_s, start) since the last compile record."""
        trace_s, lower_s = self._filed
        self._filed = (self.trace_s, self.lower_s)
        since, self._since = self._since, None
        return self.trace_s - trace_s, self.lower_s - lower_s, since

    @property
    def cache(self) -> Optional[str]:
        """The persistent cache's answers to the block's compiles as one
        word: ``hit``, ``miss``, ``mixed``, or None where it was not asked."""
        if self.hits and self.misses:
            return "mixed"
        return "hit" if self.hits else "miss" if self.misses else None

    def phases(self, seconds: float) -> Dict[str, Any]:
        """Where ``seconds``, the wall time of the block's one call, went:
        the three phases, ``other_s`` (the rest: argument handling, the
        first enqueue), and of ``compile_s`` the cache's ``cache_load_s``
        with its answer."""
        phases = {"trace_s": self.trace_s, "lower_s": self.lower_s,
                  "compile_s": self.compile_s}
        phases["other_s"] = seconds - sum(phases.values())
        phases["cache_load_s"] = self.cache_load_s
        return {**{k: round(v, 6) for k, v in phases.items()},
                "cache": self.cache}


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
    monitoring.register_event_time_span_listener(_on_time_span)


def _on_event(event: str, **_: Any) -> None:
    # Fires inside the compile, before its duration event, same thread.
    answer = _CACHE_EVENTS.get(event)
    if answer is not None:
        _thread.cache = answer


def _on_time_span(event: str, start: float, end: float, **_: Any) -> None:
    # A hybrid step's trace fires thousands of these: a thread-local read,
    # and on a thread with no label nothing more.
    ctx = getattr(_thread, "label", None)
    if ctx is not None and event in _PHASE_EVENTS:
        ctx._span(_PHASE_EVENTS[event], start, end)


def _on_duration(event: str, seconds: float, **_: Any) -> None:
    if event != _COMPILE_EVENT:
        # Inside a compile the cache answered, before the compile's own.
        kept = _CACHE_DURATIONS.get(event)
        if kept is not None:
            setattr(_thread, kept, seconds)
        return
    cache, _thread.cache = getattr(_thread, "cache", None), None
    cache_load_s, _thread.cache_load_s = \
        getattr(_thread, "cache_load_s", 0.0), 0.0
    saved_s, _thread.saved_s = getattr(_thread, "saved_s", 0.0), 0.0
    ctx = getattr(_thread, "label", None)
    shapes, shardings, donation = None, None, ()
    trace_s, lower_s, start = 0.0, 0.0, None
    if ctx is not None:
        ctx.compiles += 1
        ctx.compile_s += seconds
        ctx.cache_load_s += cache_load_s
        ctx.saved_s += saved_s
        ctx.hits += cache == "hit"
        ctx.misses += cache == "miss"
        trace_s, lower_s, start = ctx._file()
        if ctx.signature is not None:
            shapes, shardings, donation = ctx.signature()
    record_compile(UNLABELLED if ctx is None else ctx.label, shapes=shapes,
                   shardings=shardings, donation=donation, trace_s=trace_s,
                   lower_s=lower_s, compile_s=seconds, cache=cache,
                   cache_load_s=cache_load_s, saved_s=saved_s, start=start)
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
    ``attributes`` are the caller's own (where the seconds went, from the
    call's label: :meth:`compile_label.phases`; ``TrainStep``: what the
    traced code said of itself, ``util/first_call.py``)."""
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
                sum(r["trace_s"] + r["lower_s"] + r["compile_s"]
                    for r in rows), 6),
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


# ------------------------------------------------------------ set-up account

class setup_span:
    """``with setup_span("runtime.init", {...}) as attributes:`` — a
    :func:`tracing.span` (so a ``TraceAnnotation`` on the device trace's
    clock whenever a profile is open, and the exporter's span when tracing
    is on) that is also a row of :func:`setup_account` while that is open.
    The block may add to ``attributes`` before it ends.  :meth:`end` is the
    block's exit for a span that one function opens and another closes, on
    the same thread; a second call does nothing."""

    __slots__ = ("name", "attributes", "_span", "_start")

    def __init__(self, name: str,
                 attributes: Optional[Dict[str, Any]] = None):
        self.name = name
        self.attributes = {} if attributes is None else attributes
        self._span = tracing.span(name, attributes=self.attributes)
        self._start: Optional[float] = None

    def __enter__(self) -> Dict[str, Any]:
        self._start = time.time()
        self._span.__enter__()
        return self.attributes

    def __exit__(self, et, ev, tb):
        start, self._start = self._start, None
        if start is not None:
            self._span.__exit__(et, ev, tb)
            _account_row(self.name, start, time.time(), self.attributes)
        return False

    def end(self) -> None:
        self.__exit__(None, None, None)


def record_setup_span(name: str, start: float, end: float,
                      attributes: Optional[Dict[str, Any]] = None) -> None:
    """:func:`tracing.record_span` for a span of the set-up timed after the
    fact (``train.first_call``), and its row of :func:`setup_account`."""
    tracing.record_span(name, start, end, attributes=attributes)
    _account_row(name, start, end, attributes or {})


def _account_row(name: str, start: float, end: float,
                 attributes: Dict[str, Any]) -> None:
    row = {"name": name, "start": start, "end": end,
           **{k: attributes[k] for k in _ACCOUNT_KEYS if k in attributes}}
    with _lock:
        if _account_closed is None and len(_account_rows) < _ACCOUNT_ROWS:
            _account_rows.append(row)


def _process_start() -> Tuple[float, str]:
    """``time.time()`` at this process's start, from the kernel's account
    of it (``proc``); where that cannot be read, the import of ``ray_tpu``
    (``import``).  Read once."""
    global _process_started
    with _lock:
        if _process_started is None:
            imported = sys.modules["ray_tpu"].IMPORTED_AT
            _process_started = (imported, "import")
            try:
                with open("/proc/self/stat") as f:  # after "(comm)": 3rd on
                    ticks = float(f.read().rsplit(")", 1)[1].split()[19])
                with open("/proc/uptime") as f:
                    age = float(f.read().split()[0]) \
                        - ticks / os.sysconf("SC_CLK_TCK")
                if 0.0 <= age and time.time() - age <= imported:
                    _process_started = (time.time() - age, "proc")
            except (OSError, ValueError, IndexError):
                pass
        return _process_started


def close_setup_account(ts: Optional[float] = None,
                        by: str = "host") -> Optional[Dict[str, Any]]:
    """The first train step whose call compiled nothing has ended at ``ts``
    (``by``: ``device`` where the step profiler's resolver thread saw its
    sentinel ready, ``host`` where the call's return is all there is): the
    account takes no more rows, the gauge
    ``ray_tpu_train_time_to_first_step_seconds`` is set and the
    ``ray_tpu.train`` logger says at INFO where the seconds went.  Once a
    process; a later call returns None."""
    global _account_closed
    with _lock:
        if _account_closed is not None:
            return None
        _account_closed = {"ts": time.time() if ts is None else ts, "by": by}
    account = setup_account()
    TIME_TO_FIRST_STEP.set(account["to_first_step_s"])
    logging.getLogger("ray_tpu.train").info(
        "first steady step done %.2f s after the process started (%s): %s; "
        "%.2f s outside every span, the longest gap %s",
        account["to_first_step_s"], account["start_from"],
        ", ".join(f"{r['name']} {r['end'] - r['start']:.2f}"
                  for r in account["rows"]),
        account["unspanned_s"],
        max(account["gaps"], key=lambda g: g["seconds"], default=None))
    return account


def setup_account() -> Dict[str, Any]:
    """The set-up from inside the program, on ``time.time()``: ``start``
    (the process's, ``start_from`` says whence), the ``rows`` in order of
    their starts (``runtime.init``, ``train.fit_setup``,
    ``train.init_params``, ``train.init_opt_state``, ``train.first_batch``,
    ``train.first_call`` and every later call of a labelled program that
    compiled: name, start, end and what ``_ACCOUNT_KEYS`` lists of the
    span's attributes), and ``closed``: the end, on the device, of the
    first step whose call compiled nothing.  From them ``spanned_s`` (the
    union of the rows), ``gaps`` (each stretch no row covers, by the rows
    it lies between, the process's start and the first step's end
    included) and, once closed, ``to_first_step_s`` and ``unspanned_s``
    (the gaps' sum: the interpreter's start, the imports, the accelerator
    runtime coming up, and what the caller ran between the rows).  Open,
    the account ends at its last row and the two read None."""
    start, start_from = _process_start()
    with _lock:
        rows = sorted((dict(r) for r in _account_rows),
                      key=lambda r: r["start"])
        closed = dict(_account_closed) if _account_closed else None
    end = closed["ts"] if closed else max(
        (r["end"] for r in rows), default=start)
    gaps, spanned, at, last = [], 0.0, start, "process_start"
    for row in rows + [{"name": "first_step" if closed else "open",
                        "start": end, "end": end}]:
        lo, hi = max(row["start"], start), min(row["end"], end)
        if lo > at:
            gaps.append({"after": last, "before": row["name"],
                         "seconds": round(lo - at, 6)})
        if hi > at:
            spanned += hi - max(lo, at)
            at, last = hi, row["name"]
    total = end - start
    return {"start": start, "start_from": start_from, "rows": rows,
            "closed": closed, "gaps": gaps, "spanned_s": round(spanned, 6),
            "to_first_step_s": round(total, 6) if closed else None,
            "unspanned_s": round(total - spanned, 6) if closed else None}


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
    transfer tail, the set-up account's rows and its close (it is open
    again, from the same process start).  The jax listeners stay
    registered."""
    with _lock:
        _last_sig.clear()
        _compile_tail.clear()
        _recompile_ts.clear()
        _programs.clear()
        _first_calls.clear()
        _transfer_tail.clear()
        _pools.clear()
        _account_rows.clear()
        global _storms, _account_closed
        _storms = 0
        _account_closed = None
    POOL_BYTES.clear()
    POOL_PEAK_BYTES.clear()
    HBM_BYTES.clear()
    HBM_PEAK_BYTES.clear()
