"""Per-step train profiler: wall-time attribution, live MFU, step spans.

Answers the ROADMAP item-4 question ("where does the non-compute time
go?") continuously instead of via one-shot probe scripts: every training
step's wall clock — one ``report()`` to the next — is attributed into

* ``data_wait``   — blocked on the input pipeline (prefetch starvation,
  elastic ledger claim + fetch);
* ``h2d``         — host→device transfer dispatch
  (:class:`~ray_tpu.data.ingest.prefetch.DeviceBatchIterator`);
* ``collective``  — gradient-sync rendezvous (entering a collective to
  getting its result back);
* ``ckpt_block``  — the device→host snapshot an async checkpoint save
  blocks the step for (:meth:`ShardWriter.save_async`);
* ``compute``     — the residual.  Defining compute as ``wall − Σ other``
  makes the buckets sum to the measured wall time *by construction* —
  un-instrumented host work lands in compute rather than vanishing.

Beside the wait buckets each row carries flat **counters** (:data:`COUNTERS`)
that are *not* waits and stay out of the sum: ``dispatch`` and ``report``
(host seconds inside ``TrainStep.__call__`` and ``train.report``),
``hand_over`` (host seconds ``TrainStep`` then spends giving this profiler
the step's sentinel: what the device facts below cost the worker's thread),
``h2d_bytes`` (what ``device_put_batch`` sent), ``compiles`` and
``compile_s`` (jax compile events on the worker's thread, via
``device_telemetry``).  A steady step has ``compiles == 0``.

**What the device made of the step** comes onto the row late.  ``wall`` is
the cadence of ``report()``, while the host may run several steps ahead of
the device, so it is no step time.  After each dispatch ``TrainStep`` hands
the profiler a *sentinel* (:meth:`StepProfiler.dispatched`): a fresh output
of the step, the read of its counter ref where it has one, else the loss.
One daemon thread a profiler waits for the sentinels in order (span
``train.step_done``) and the row of the step the dispatch belongs to gains
:data:`DEVICE_KEYS`: ``done`` (``time.perf_counter()`` when the wait
returned), ``device_period`` (seconds since the previous sentinel's
``done``: the step's time on the device while the device never waits),
``in_flight`` (the steps dispatched before this one that the device had not
finished when it was dispatched: 0 means the chip had nothing to do), and
the step's counters (``tracing.STEP_COUNTER_REGISTRY``: ``moe_rows`` as
nested lists).  Nothing blocks on the worker's thread.  Reading
:attr:`StepProfiler.history` waits for what is pending, so its rows are
whole; a row of a step that dispatched nothing through ``TrainStep`` has
none of these keys.

The profiler is **per worker thread** (thread-local, like the session it
belongs to), so ``record()`` needs no lock: every hook site — prefetcher
consumption, device transfer, collective contribute, snapshot — runs on
the worker's own thread.  The resolver thread touches no row: it hands
what it found over through a queue, and whoever next closes a step or
reads ``history`` merges it in under a lock.  Hook modules outside ``train/`` reach it
through a ``sys.modules`` probe (see :func:`record`'s callers), so they
never import the train package and pay one dict lookup when training is
not in the process at all.

Step closure (``step_boundary``, called from ``TrainSession.report``)
emits the PR 4 span machinery retroactively — a ``train.step`` parent
span with one child span per recorded interval — and refreshes the
``ray_tpu_train_*`` bucket gauges (host attribution: they sum to ``wall``).
The step-time gauges (seconds, p50/p95, tokens/s, MFU, steps in flight, what
``metrics.COUNTER_GAUGES`` derives from a step counter) are refreshed once a
row, when the device facts of all its dispatches have arrived, from its
``device_period``; a step with no sentinel falls back to ``wall``.  Spans
cost nothing when tracing is off; the whole profiler, the thread and the
sentinels with it, is skipped when ``RunConfig(profile=False)``.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.train import metrics as train_metrics
from ray_tpu.util import tracing, watchdog

#: Attribution buckets measured by hooks; ``compute`` is the residual.
BUCKETS = ("data_wait", "h2d", "collective", "ckpt_block")

#: Host-busy seconds and counts a row carries beside the buckets; they are
#: no part of ``compute = wall - sum(buckets)``.  ``report`` is written by
#: ``TrainSession.report`` onto the row its own step boundary closed.
COUNTERS = ("dispatch", "hand_over", "report", "h2d_bytes", "compiles",
            "compile_s")

#: What a row gains once the device has finished its step, beside the
#: step's counters; absent on a row whose step handed over no sentinel.
DEVICE_KEYS = ("done", "device_period", "in_flight")

#: Per-bucket cap on *span* intervals kept per step — totals always
#: accumulate, but a step with thousands of tiny waits must not emit
#: thousands of spans.
_MAX_INTERVALS = 64

#: Recent step times for the live p50/p95 gauges (sliding, not lifetime —
#: a regression shows up within a window, not diluted by history).
_PCTL_WINDOW = 128

_local = threading.local()


class StepProfiler:
    """Wall-time attribution for one worker's training steps.

    Lives on the worker's :class:`~ray_tpu.train.session.TrainSession`;
    activated/deactivated with the session itself (``init_session`` /
    ``clear_session``).  All methods are called from the worker thread.
    """

    def __init__(self, run_name: str = "", rank: int = 0,
                 flops_per_step: Optional[float] = None,
                 tokens_per_step: Optional[float] = None,
                 peak_flops: Optional[float] = None,
                 history_steps: int = 512):
        self.run_name = run_name
        self.rank = rank
        self.flops_per_step = flops_per_step
        self.tokens_per_step = tokens_per_step
        self.peak_flops = peak_flops
        self._history: "deque" = deque(maxlen=history_steps)
        # Lock-free by thread-local discipline: the profiler is reached
        # through ``_local`` so every hook site runs on the worker's own
        # thread — the ownership labels document (and let the analyzer
        # police) that no spawned thread may touch the step state.
        self._step = 0  # owned_by_thread: worker thread (thread-local _local)
        self._step_start: Optional[float] = None  # owned_by_thread: worker thread (thread-local _local)
        self._totals: Dict[str, float] = {b: 0.0 for b in BUCKETS}  # owned_by_thread: worker thread (thread-local _local)
        self._intervals: Dict[str, List[Tuple[float, float]]] = {  # owned_by_thread: worker thread (thread-local _local)
            b: [] for b in BUCKETS}
        self._counts: Dict[str, float] = {c: 0 for c in COUNTERS}  # owned_by_thread: worker thread (thread-local _local)
        self._dispatches = 0  # owned_by_thread: worker thread (thread-local _local)
        #: sentinels dispatched and not yet seen ready, oldest first
        self._outstanding: "deque" = deque()  # owned_by_thread: worker thread (thread-local _local)
        # The hand-over: the worker puts (step, in_flight, sentinel,
        # counters) on _pending; the resolver thread, which owns nothing
        # else here, answers with (step, facts) on _resolved; _merge puts
        # the facts into the rows, under _merge_lock since history may be
        # read from any thread.
        self._pending: "queue.Queue" = queue.Queue()
        self._resolved: "deque" = deque(maxlen=8 * history_steps)
        self._merge_lock = threading.Lock()
        self._resolver: Optional[threading.Thread] = None  # owned_by_thread: worker thread (thread-local _local)
        self._recent_steps: "deque" = deque(maxlen=_PCTL_WINDOW)  # guarded_by: _merge_lock
        #: closed step -> its dispatches the resolver has yet to answer
        self._unanswered: Dict[int, int] = {}  # guarded_by: _merge_lock

    # ------------------------------------------------------------- config
    def configure(self, *, flops_per_step: Optional[float] = None,
                  tokens_per_step: Optional[float] = None,
                  peak_flops: Optional[float] = None) -> None:
        """Set the MFU/throughput inputs (typically once, from inside the
        train loop, after the model is built)."""
        if flops_per_step is not None:
            self.flops_per_step = float(flops_per_step)
        if tokens_per_step is not None:
            self.tokens_per_step = float(tokens_per_step)
        if peak_flops is not None:
            self.peak_flops = float(peak_flops)

    # -------------------------------------------------------------- hooks
    def start(self, now: Optional[float] = None) -> None:
        """Open the first step window (activation time)."""
        if self._step_start is None:
            self._step_start = time.time() if now is None else now

    def record(self, bucket: str, start: float, end: float) -> None:
        """Attribute [start, end] (``time.time()`` seconds) to a bucket.

        Called from the hook sites on the worker thread; must stay cheap
        — two dict lookups, an add and (usually) an append."""
        dur = end - start
        if dur <= 0.0:
            return
        self._totals[bucket] += dur
        iv = self._intervals[bucket]
        if len(iv) < _MAX_INTERVALS:
            iv.append((start, end))
        if self._step_start is None:
            self._step_start = start

    def count(self, counter: str, amount: float) -> None:
        """Add to one of the current step's :data:`COUNTERS`."""
        self._counts[counter] += amount

    def dispatched(self, sentinel, counters: Dict[str, Any]) -> None:
        """``TrainStep`` has dispatched a step of the open row: ``sentinel``
        is a fresh output of it (a ``jax.Array`` no later call donates),
        ``counters`` its step counters by name, still on the device.  Costs
        the worker one ``is_ready()`` for each step still in flight and a
        queue put; the wait is the resolver thread's."""
        flying = self._outstanding
        while flying and flying[0].is_ready():
            flying.popleft()
        self._pending.put((self._step, len(flying), sentinel, counters))
        flying.append(sentinel)
        self._dispatches += 1
        if self._resolver is None:
            self._resolver = threading.Thread(
                target=self._resolve_loop, daemon=True,
                name=f"train-step-done:{self.run_name}:{self.rank}")
            self._resolver.start()

    def when_resolved(self, then) -> None:
        """``then(ts)`` once every sentinel handed over so far has been
        seen ready, on the resolver thread, ``ts`` being ``time.time()``
        when the last of them was; at once where none is pending."""
        if self._resolver is None:
            then(time.time())
        else:
            self._pending.put(then)

    def _resolve_loop(self) -> None:
        """The resolver thread: sentinels in dispatch order, each waited
        for (the GIL is released meanwhile), stamped, its counters copied
        to the host.  A step that failed on the device (jax's
        ``RuntimeError``) resolves to no facts; the failure is the step
        loop's to raise.  Anything else is a fault of this code: logged,
        and the thread goes on, since ``history`` waits for its answers."""
        previous = None
        done_at = time.time()  # the last sentinel's ``done``, on the wall
        while True:
            item = self._pending.get()
            if item is None:
                self._pending.task_done()
                return
            if callable(item):  # when_resolved's
                try:
                    item(done_at)
                except Exception:  # noqa: BLE001
                    logging.getLogger(__name__).exception(
                        "when_resolved: %r failed", item)
                self._pending.task_done()
                continue
            step, in_flight, sentinel, counters = item
            try:
                with tracing.annotate("train.step_done"):
                    sentinel.block_until_ready()
                done, done_at = time.perf_counter(), time.time()
                facts = {"done": done, "in_flight": in_flight,
                         "device_period": None if previous is None
                         else done - previous}
                facts.update((name, _to_lists(value))
                             for name, value in counters.items())
                previous = done
            except RuntimeError:
                facts = None
            except Exception:  # noqa: BLE001
                logging.getLogger(__name__).exception(
                    "step %d: its device facts are lost", step)
                facts = None
            self._resolved.append((step, facts))
            self._pending.task_done()

    def close(self) -> None:
        """End the resolver thread once it has answered what is pending."""
        if self._resolver is not None:
            self._resolver = None
            self._pending.put(None)

    @property
    def history(self) -> "deque":
        """Per-step attribution rows (bounded) — the bench and the state
        API read these; each row's buckets sum to its wall.  Waits for the
        sentinels handed over so far, so the rows come back whole."""
        self._pending.join()
        self._merge()
        return self._history

    def _merge(self) -> None:
        """Put what the resolver thread has answered into the rows of the
        closed steps; a row that has all its answers refreshes the
        step-time gauges."""
        with self._merge_lock:
            while self._resolved and self._resolved[0][0] < self._step:
                step, facts = self._resolved.popleft()
                at = step - self._history[0]["step"] if self._history else -1
                row = self._history[at] if 0 <= at < len(self._history) \
                    else None  # dropped from the history already
                if row is not None and facts is not None:
                    # several dispatches in one step: the last one's done
                    # and counters, the first one's in_flight, the periods
                    # summed
                    if row.get("device_period") is not None:
                        facts["device_period"] = row["device_period"] \
                            + (facts["device_period"] or 0.0)
                    facts["in_flight"] = row.get("in_flight",
                                                 facts["in_flight"])
                    row.update(facts)
                left = self._unanswered.pop(step, 1) - 1
                if left:
                    self._unanswered[step] = left
                elif row is not None and "done" in row:
                    self._observe_row(row)

    def _observe_row(self, row: dict) -> None:  # requires_lock: _merge_lock
        """The gauges that read a whole row's device facts."""
        if row["device_period"] is not None:
            self._observe_step(row["device_period"])
        train_metrics.STEPS_IN_FLIGHT.set(row["in_flight"])
        for name, (gauge, derive) in train_metrics.COUNTER_GAUGES.items():
            value = derive(row[name]) if name in row else None
            if value is not None:
                gauge.set(value)

    # ----------------------------------------------------------- boundary
    def step_boundary(self, now: Optional[float] = None) -> Optional[dict]:
        """Close the current step: attribute its wall, emit spans, refresh
        the live gauges.  Returns the attribution row (or None before the
        first window opened)."""
        t1 = time.time() if now is None else now
        t0 = self._step_start
        if t0 is None or t1 <= t0:
            self._reset(t1)
            return None
        wall = t1 - t0
        totals = {b: min(self._totals[b], wall) for b in BUCKETS}
        compute = max(0.0, wall - sum(totals.values()))
        row = {"step": self._step, "wall": wall, "compute": compute,
               **totals, **self._counts}
        with self._merge_lock:  # _merge indexes the deque by step
            self._history.append(row)
            if self._dispatches:
                self._unanswered[self._step] = self._dispatches
        # Progress heartbeat: step closure feeds the hang watchdog (stall
        # = beats stop) and the straggler check (cross-worker dispersion
        # of these walls).
        watchdog.beat(f"train:{self.run_name}:{self.rank}", wall=wall)
        self._emit_spans(t0, t1, compute, row)
        self._update_metrics(wall, totals, row)
        self._step += 1
        self._dispatches = 0
        self._reset(t1)
        self._merge()
        return row

    def _reset(self, t1: float) -> None:
        self._step_start = t1
        for b in BUCKETS:
            self._totals[b] = 0.0
            self._intervals[b].clear()
        for c in COUNTERS:
            self._counts[c] = 0

    # -------------------------------------------------------------- spans
    def _emit_spans(self, t0: float, t1: float, compute: float,
                    row: dict) -> None:
        if not tracing.is_tracing_enabled():
            return
        parent = tracing.record_span(
            "train.step", t0, t1,
            attributes={"step": row["step"], "rank": self.rank,
                        "run": self.run_name,
                        "compute_s": round(compute, 6)})
        if parent is None:
            return
        iv = self._intervals
        tracing.record_span_batch(
            "train.data_wait", [(s, e, parent) for s, e in iv["data_wait"]])
        tracing.record_span_batch(
            "train.h2d", [(s, e, parent) for s, e in iv["h2d"]])
        tracing.record_span_batch(
            "train.collective",
            [(s, e, parent) for s, e in iv["collective"]])
        tracing.record_span_batch(
            "train.ckpt_block",
            [(s, e, parent) for s, e in iv["ckpt_block"]])

    # ------------------------------------------------------------- gauges
    def _update_metrics(self, wall: float, totals: Dict[str, float],
                        row: dict) -> None:
        m = train_metrics
        m.STEPS_PROFILED.inc()
        m.DATA_STARVED_FRACTION.set(totals["data_wait"] / wall)
        for bucket, dur in totals.items():
            m.STEP_BUCKET_SECONDS.set(dur, {"bucket": bucket})
        m.STEP_BUCKET_SECONDS.set(row["compute"], {"bucket": "compute"})
        if not self._dispatches:
            # no sentinel will tell this step's time: the host's clock
            with self._merge_lock:
                self._observe_step(wall)

    def _observe_step(self, seconds: float) -> None:  # requires_lock: _merge_lock
        """One step took ``seconds``: on the device where a sentinel said
        so (``device_period``), else report() to report()."""
        m = train_metrics
        m.STEP_SECONDS.observe(seconds)
        self._recent_steps.append(seconds)
        recent = sorted(self._recent_steps)
        m.STEP_P50_SECONDS.set(recent[len(recent) // 2])
        m.STEP_P95_SECONDS.set(recent[min(len(recent) - 1,
                                          int(len(recent) * 0.95))])
        if self.tokens_per_step:
            m.TOKENS_PER_SECOND.set(self.tokens_per_step / seconds)
        if self.flops_per_step and self.peak_flops:
            m.MFU.set(self.flops_per_step / seconds / self.peak_flops)

    # ------------------------------------------------------------ queries
    def last_attribution(self) -> Optional[dict]:
        """The newest closed row as it stands: device facts only if they
        have arrived (this does not wait)."""
        self._merge()
        return self._history[-1] if self._history else None


def _to_lists(value) -> list:
    """A step counter on the host, as nested lists.  Across processes a
    counter's ref is replicated (``make_train_step``), so any local copy
    holds all of it."""
    if not getattr(value, "is_fully_addressable", True):
        value = value.addressable_shards[0].data
    return np.asarray(value).tolist()


# ---------------------------------------------------------------- thread API
def activate(profiler: Optional[StepProfiler]) -> None:
    """Bind a profiler to the calling thread (the session lifecycle calls
    this; ``None`` unbinds)."""
    old = getattr(_local, "profiler", None)
    if old is not None and old is not profiler:
        old.close()
    _local.profiler = profiler
    if profiler is not None:
        profiler.start()


def active_profiler() -> Optional[StepProfiler]:
    return getattr(_local, "profiler", None)


def record(bucket: str, start: float, end: float) -> None:
    """Hook entry point: attribute an interval to the calling thread's
    profiler; no-op when the thread isn't a profiled train worker.

    Modules outside ``train/`` must not import this package for it (the
    train package import pulls the trainer → collective chain); they probe
    ``sys.modules.get("ray_tpu.train.profiler")`` instead — if the module
    was never imported, no profiler can be active anywhere.
    """
    p = getattr(_local, "profiler", None)
    if p is not None:
        p.record(bucket, start, end)


def count(counter: str, amount: float) -> None:
    """Hook entry point for the row's :data:`COUNTERS`; same probing rule
    and the same no-op off a profiled train worker's thread as
    :func:`record`."""
    p = getattr(_local, "profiler", None)
    if p is not None:
        p.count(counter, amount)


def configure(**kwargs: Any) -> None:
    """Set MFU/throughput inputs on the calling worker's profiler (no-op
    outside a profiled train loop) — see :meth:`StepProfiler.configure`."""
    p = getattr(_local, "profiler", None)
    if p is not None:
        p.configure(**kwargs)
