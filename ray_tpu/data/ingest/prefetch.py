"""Batch prefetch: host-side buffering + double-buffered device transfer.

Two stages, independently optional:

* :class:`HostPrefetcher` — a background thread pulls batches out of the
  (backpressured) ingest pipeline into a bounded queue so block fetch /
  shuffle / rebatch latency overlaps the training step.  Occupancy and
  starved-seconds are exported as metrics: occupancy pinned at 0 plus a
  growing starved counter is the "input-bound" signature.
* :class:`DeviceBatchIterator` — dispatches ``jax.device_put`` of batch
  N+1 while the caller steps on batch N (JAX transfers are asynchronous,
  so the dispatch returns immediately and the copy proceeds during the
  step).  With a ``sharding`` (e.g. ``mesh.batch_sharding(mesh)``) the
  arrays land already laid out for the step's NamedSharding — no repack
  on first use.
"""

from __future__ import annotations

import queue
import sys
import threading
import time
from typing import Any, Dict, Iterable, Iterator, Optional

from ray_tpu._private import fault_injection
from ray_tpu.data.ingest import metrics as ingest_metrics
from ray_tpu.exceptions import WorkerCrashedError
from ray_tpu.util import tracing

_END = ("end", None)


def _profiler_record(bucket: str, start: float, end: float) -> None:
    """Attribute an interval to the train step profiler when one is active
    on this thread (the consumer side of the pipeline IS the train worker
    thread).  Probed via sys.modules — the data layer must not import the
    train package (trainer -> collective import chain), and if the
    profiler module was never imported, none can be active."""
    mod = sys.modules.get("ray_tpu.train.profiler")
    if mod is not None:
        mod.record(bucket, start, end)


class HostPrefetcher:
    """Pull ``src`` on a daemon thread into a queue of ``depth`` batches.

    Errors from the pipeline propagate to the consumer at the point they
    occurred in the stream (never silently truncate an epoch); ``close()``
    releases the pump thread even when the consumer abandons the iterator
    mid-epoch (elastic stop, grow boundary).
    """

    def __init__(self, src: Iterable[Any], depth: int = 2,
                 should_stop=None):
        # ``_q`` and ``_stop`` are the only pump<->consumer channels
        # (thread-safe by construction); the source iterator itself is
        # advanced exclusively on the pump thread.
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._should_stop = should_stop
        self._thread = threading.Thread(
            target=self._pump, args=(iter(src),), daemon=True,
            name="ingest-prefetch")
        self._thread.start()

    def _pump(self, src: Iterator[Any]) -> None:
        try:
            while True:
                # This thread shares the GIL with the step loop: its pull
                # is a span, so a profile shows what the host was doing
                # while the worker's thread stalled.
                with tracing.annotate("data.pump"):
                    item = next(src, _END)
                if item is _END:
                    break
                if not self._put(("item", item)):
                    return
                ingest_metrics.PREFETCH_OCCUPANCY.set(self._q.qsize())
            self._put(_END)
        except BaseException as e:  # noqa: BLE001 — must reach the consumer
            self._put(("error", e))

    def _put(self, msg) -> bool:
        """Bounded put that aborts when the consumer closed us — an
        abandoned epoch must not leave a thread parked on a full queue."""
        while not self._stop.is_set():
            try:
                self._q.put(msg, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def __iter__(self) -> Iterator[Any]:
        try:
            while True:
                try:
                    kind, item = self._q.get_nowait()
                except queue.Empty:
                    # The step outran the pipeline: blocked-here time IS
                    # input starvation.  Once the session is stopped AND
                    # the pipe stays dry past a grace window, stop waiting
                    # — the pump is wedged on something a teardown already
                    # gave up on (a graceful grow drain keeps yielding, so
                    # it never trips this).
                    t0 = time.monotonic()
                    with tracing.annotate("train.data_wait"):
                        kind, item = self._starved_get(t0)
                    starved = time.monotonic() - t0
                    ingest_metrics.STARVED_SECONDS.inc(starved)
                    w1 = time.time()
                    _profiler_record("data_wait", w1 - starved, w1)
                ingest_metrics.PREFETCH_OCCUPANCY.set(self._q.qsize())
                if kind == "end":
                    return
                if kind == "error":
                    raise item
                yield item
        finally:
            self.close()

    def _starved_get(self, t0: float):
        while True:
            try:
                return self._q.get(timeout=0.5)
            except queue.Empty:
                if (self._should_stop is not None
                        and self._should_stop()
                        and time.monotonic() - t0 > 5.0):
                    from ray_tpu.data.ingest.executor import IngestAborted

                    raise IngestAborted(
                        "session stopped while the prefetch queue was "
                        "starved")

    def close(self) -> None:
        self._stop.set()
        # Drain so a pump blocked on a full queue observes the stop at its
        # next timeout tick and exits.
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass


class DeviceBatchIterator:
    """Double-buffered host->device transfer over a batch iterator.

    Yields batch N only after batch N+1's transfer has been *dispatched*
    — with JAX's async dispatch the copy overlaps the consumer's step on
    batch N.  ``sharding`` (a NamedSharding, e.g. from
    ``ray_tpu.parallel.mesh.batch_sharding``) places each numeric column
    directly into the step's layout; without one, arrays go to the
    default device.  Non-numeric columns pass through on host.
    """

    def __init__(self, batches: Iterable[Dict[str, Any]], *,
                 sharding: Any = None):
        self._src = batches
        self._sharding = sharding

    def _transfer(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        from ray_tpu._private import jax_compat

        w0 = time.time()
        try:
            with tracing.span("data.prefetch"):
                last: Optional[BaseException] = None
                for _attempt in range(2):
                    try:
                        fault_injection.check("data_ingest_prefetch")
                        return jax_compat.device_put_batch(
                            batch, sharding=self._sharding,
                            transfer_src="ingest_prefetch")
                    except WorkerCrashedError as e:
                        last = e
                raise last  # type: ignore[misc]
        finally:
            _profiler_record("h2d", w0, time.time())

    def __iter__(self) -> Iterator[Dict[str, Any]]:
        it = iter(self._src)
        try:
            cur = self._transfer(next(it))
        except StopIteration:
            return
        for nxt in it:
            nxt_dev = self._transfer(nxt)  # dispatch N+1 before yielding N
            yield cur
            cur = nxt_dev
        yield cur
