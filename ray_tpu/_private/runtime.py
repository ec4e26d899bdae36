"""The core runtime: task manager, actor manager, dispatcher, object plane.

This is the TPU-native equivalent of the reference's CoreWorker + raylet pair
(ref: src/ray/core_worker/core_worker.h:166, src/ray/raylet/node_manager.h:117),
collapsed into one in-process control plane:

* TaskManager — pending task bookkeeping, retries, lineage-based object
  reconstruction (ref: task_manager.h:212, object_recovery_manager.h:38).
* Dispatcher — dependency wait (ref: dependency_manager.h:49) then resource
  acquisition via the ClusterScheduler, then execution on the thread tier or
  a leased process worker (ref: local_task_manager.h:58, worker_pool.h:216).
* ActorManager — actor FSM with restarts (ref: gcs_actor_manager.h:312),
  ordered mailboxes, async actors, named actor registry.
* Driver API — get/put/wait/cancel/kill with in-task resource release during
  blocking get (the reference's "worker blocked in ray.get" CPU release).

Why one process: on a TPU host, exactly one JAX client owns the chips
(multi-controller SPMD), so the natural worker model is threads sharing that
client for anything touching the TPU, with process isolation as an opt-in for
CPU-bound Python.  Multi-host is reached through jax.distributed + the
collective layer, not by forking per-device workers.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import inspect
import os
import queue
import threading
import time
import traceback
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ray_tpu._private import serialization
from ray_tpu._private.config import GLOBAL_CONFIG, Config
from ray_tpu._private.ids import (
    ActorID,
    JobID,
    NodeID,
    ObjectID,
    TaskID,
    WorkerID,
    put_counter,
)
from ray_tpu._private.object_ref import ObjectRef, global_refcounter
from ray_tpu._private.object_store import ObjectStore
from ray_tpu._private.process_pool import ProcessPool
from ray_tpu._private.scheduling import (
    ClusterScheduler,
    DefaultStrategy,
    PlacementGroupSchedulingStrategy,
    SchedulingStrategy,
    SpreadStrategy,
)
from ray_tpu._private.task_spec import (ActorSpec, TaskSpec,
                                        EXEC_FN_METHOD)
from ray_tpu._private import metrics_agent
from ray_tpu.util import tracing
from ray_tpu.exceptions import (
    ActorDiedError,
    ActorUnavailableError,
    GetTimeoutError,
    ObjectLostError,
    TaskCancelledError,
    TaskError,
    WorkerCrashedError,
)

_runtime_lock = threading.Lock()
_runtime: Optional["Runtime"] = None

#: Dispatcher wake token: retry the blocked list (see _notify_resources_freed).
_RETRY_BLOCKED = object()


def _noop() -> None:
    """Stand-in release for dispatches that hold no per-task lease
    (actor calls ride the actor's standing lease)."""

_task_ctx = threading.local()


class TaskContext:
    """Per-execution context (ref: runtime_context.py RuntimeContext)."""

    __slots__ = ("task_id", "actor_id", "lease_release", "lease_reacquire", "cancelled")

    def __init__(self, task_id: TaskID, actor_id: Optional[ActorID] = None,
                 lease_release=None, lease_reacquire=None):
        self.task_id = task_id
        self.actor_id = actor_id
        self.lease_release = lease_release
        self.lease_reacquire = lease_reacquire
        self.cancelled = threading.Event()


def current_task_context() -> Optional[TaskContext]:
    return getattr(_task_ctx, "ctx", None)


class ObjectRefGenerator:
    """Streaming generator returns (ref: _raylet.pyx streaming generator
    protocol :1097/:1348): yields ObjectRefs as the remote generator yields."""

    def __init__(self, task_id: TaskID):
        self._task_id = task_id
        self._queue: "queue.Queue" = queue.Queue()
        self._done = False

    def _push(self, ref: ObjectRef) -> None:
        self._queue.put(ref)

    def _finish(self, error: Optional[BaseException] = None) -> None:
        self._queue.put(StopIteration if error is None else error)

    def __iter__(self):
        return self

    def __next__(self) -> ObjectRef:
        item = self._queue.get()
        if item is StopIteration:
            self._queue.put(StopIteration)
            raise StopIteration
        if isinstance(item, BaseException):
            self._queue.put(item)
            raise item
        return item

    def __aiter__(self):
        return self

    async def __anext__(self):
        loop = asyncio.get_event_loop()
        try:
            return await loop.run_in_executor(None, self.__next__)
        except StopIteration:
            raise StopAsyncIteration from None


class _ActorState:
    PENDING = "PENDING_CREATION"
    ALIVE = "ALIVE"
    RESTARTING = "RESTARTING"
    DEAD = "DEAD"

    def __init__(self, spec: ActorSpec):
        self.spec = spec
        self.state = _ActorState.PENDING
        self.instance: Any = None
        # SimpleQueue: C-implemented put/get — roughly half the wakeup cost
        # of queue.Queue's pure-Python Condition dance on the actor-call
        # hot path (same FIFO + blocking semantics; we never need join()).
        self.mailbox: "queue.SimpleQueue" = queue.SimpleQueue()
        self.threads: List[threading.Thread] = []
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self.node_id: Optional[NodeID] = None
        self.release = None
        self.num_restarts = 0
        self.death_cause: Optional[BaseException] = None
        self.ready_event = threading.Event()
        self.lock = threading.Lock()
        self.is_async = any(
            inspect.iscoroutinefunction(getattr(spec.cls, m, None))
            for m in dir(spec.cls)
            if not m.startswith("__") or m == "__call__"
        )
        #: Dedicated process worker hosting the instance when
        #: isolation="process" or a runtime_env is set (see _start_actor).
        self.proc_worker = None
        #: Worker node hosting the instance when placement landed on a
        #: joined remote node (None = this process hosts it).
        self.remote_node: Optional[NodeID] = None


class _LeanExecPool:
    """Futures-free task executor: SimpleQueue dispatch to daemon threads,
    spawning a new thread only when none is idle (bounded).  Replaces
    ThreadPoolExecutor on the task hot path — its per-submit Future +
    semaphore + thread-adjust machinery cost ~75us/task (bench_core
    single_client_tasks_async); every call site ignores the result anyway."""

    def __init__(self, max_threads: int = 512, name: str = "worker"):
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._max = max_threads
        self._name = name
        #: Workers parked in q.get() whose NEXT wake-up has not been claimed
        #: by a submit.  Every queued item holds exactly one claim (an idle
        #: permit or a freshly spawned thread), so no item can be stranded —
        #: a plain "is anyone idle" read could leave one behind when two
        #: submits race, deadlocking nested tasks.
        self._idle = 0
        self._nthreads = 0
        self._threads: List[threading.Thread] = []
        self._stopped = False
        self._lock = threading.Lock()

    def submit(self, fn, *args, **kwargs) -> None:
        with self._lock:
            if self._stopped:
                # Loud, like ThreadPoolExecutor: silently dropping would leak
                # the caller's already-acquired lease and hang its waiters.
                raise RuntimeError("cannot submit after shutdown")
            if self._idle > 0:
                self._idle -= 1  # claim a parked worker's next wake
            elif self._nthreads < self._max:
                self._nthreads += 1
                t = threading.Thread(
                    target=self._run,
                    name=f"{self._name}-{self._nthreads}",
                    daemon=True,
                )
                self._threads.append(t)
                t.start()
            # else: at capacity — an active worker will claim it via the
            # idle+1 it posts after finishing its current item.
        self._q.put((fn, args, kwargs))

    def _run(self) -> None:
        # A new thread's first wake is pre-claimed by the submit that
        # spawned it, so it parks WITHOUT posting an idle permit.
        while True:
            item = self._q.get()
            if item is None:
                with self._lock:
                    self._nthreads -= 1
                return
            fn, args, kwargs = item
            try:
                fn(*args, **kwargs)
            except BaseException:  # noqa: BLE001 — never kill the pool thread
                import traceback

                traceback.print_exc()
            with self._lock:
                if self._stopped:
                    self._nthreads -= 1
                    return
                self._idle += 1

    def shutdown(self, wait: bool = False, cancel_futures: bool = False) -> None:
        with self._lock:
            self._stopped = True
            n = self._nthreads
            self._idle = 0
            threads = list(self._threads)
        if cancel_futures:
            # Drop queued-but-undispatched work so nothing runs against a
            # torn-down runtime after this returns.
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
        for _ in range(n):
            self._q.put(None)
        if wait:
            for t in threads:
                t.join(timeout=5)


class Runtime:
    """Singleton per process; created by ray_tpu.init()."""

    def __init__(
        self,
        num_cpus: Optional[float] = None,
        num_tpus: Optional[float] = None,
        resources: Optional[Dict[str, float]] = None,
        labels: Optional[Dict[str, str]] = None,
        _system_config: Optional[dict] = None,
        namespace: str = "default",
    ):
        GLOBAL_CONFIG.apply_overrides(_system_config)
        self.config: Config = GLOBAL_CONFIG
        # Chaos layer (ref: rpc_chaos.h RpcFailure): rebuild from the fresh
        # config; hot paths skip the hooks entirely when disabled.
        from ray_tpu._private import fault_injection

        fault_injection.reset_injector()
        self._chaos = fault_injection.get_injector().enabled
        # Black-box bootstrap: the flight recorder's span tap only exists
        # once the singleton does — building it here (not lazily at the
        # first dump) is what makes the ring *always-on*: spans emitted
        # before any failure seam fires must already be in it.  The
        # watchdog ticker starts here too: its tick is what samples metric
        # deltas into the ring, so without it a process with tracing off
        # (the default) would crash with an empty black box.
        from ray_tpu.util import flight_recorder, watchdog

        flight_recorder.get_recorder()
        flight_recorder.record_event(
            "runtime.start", {"pid": os.getpid()}, kind="state")
        watchdog.get_watchdog().ensure_started()
        self.job_id = JobID.from_random()
        self.worker_id = WorkerID.from_random()
        self.namespace = namespace

        self.store = ObjectStore(self.config.object_store_memory)
        self.scheduler = ClusterScheduler()
        self.process_pool = ProcessPool(self.store.arena_path, self.store.plasma)
        self.refcounter = global_refcounter()
        self.refcounter.set_zero_callback(self._on_zero_refs)

        # Node-to-node object plane (ref: object_manager.h:117) — opt-in: the
        # server makes refs leaving this process carry a routable owner
        # address; the pull manager fetches remote-owned refs on demand.
        self.object_server = None
        self._pull_mgr = None
        # Owner-side BorrowLedger — built eagerly: three threads (object
        # server ADD/RELEASE/FREE handlers) race to touch it, and a lazy
        # check-then-create could lose a concurrent borrow registration.
        from ray_tpu._private.borrowing import BorrowLedger

        self._borrows = BorrowLedger()
        #: Cross-language registry + a bounded pin window for results the
        #: foreign caller hasn't pulled yet (see register_cross_lang).
        self._cross_lang_fns: Dict[str, Any] = {}
        self._cross_lang_results: deque = deque(maxlen=256)

        # OOM defense over busy process workers (ref: memory_monitor.h:52).
        self._leased_workers: Dict[int, "_LeasedWorker"] = {}
        self._leased_lock = threading.Lock()
        self._memory_monitor = None
        if self.config.enable_object_transfer:
            self.start_object_server()

        # Cross-host worker nodes (ref: node_manager.h:117): joined nodes,
        # their in-flight dispatches, and the location table for results
        # that STAYED in a producing node's store (direct-call split).
        self.node_server = None
        self._remote_nodes: Dict[NodeID, Any] = {}
        self._remote_nodes_lock = threading.Lock()
        self._remote_inflight: Dict[TaskID, Tuple] = {}
        self._remote_lock = threading.Lock()
        self._object_locations: Dict[ObjectID, str] = {}
        self._locations_lock = threading.Lock()
        #: Waiters blocked until an object resolves EITHER locally or as a
        #: remote location (_wait_value_or_location); fired by
        #: _on_object_ready so the wake is event-driven, not polled.
        self._ready_events: Dict[ObjectID, threading.Event] = {}
        self._export_release_q: Optional["queue.SimpleQueue"] = None

        # Head node resources.
        from ray_tpu._private.accelerators import detect_accelerators

        base: Dict[str, float] = {"CPU": float(num_cpus if num_cpus is not None else (os.cpu_count() or 1))}
        accel_res, accel_labels = detect_accelerators()
        if num_tpus is not None:
            accel_res["TPU"] = float(num_tpus)
        base.update(accel_res)
        base.update(resources or {})
        base.setdefault("memory", float(self.store.capacity_bytes))
        node_labels = dict(accel_labels)
        node_labels.update(labels or {})
        self.head_node_id = self.scheduler.add_node(base, node_labels)

        # Task bookkeeping.
        self._lineage: Dict[ObjectID, TaskSpec] = {}
        # RLock: a lineage pop can GC an ObjectRef whose zero-callback
        # re-enters _on_zero_refs on this same thread.
        self._lineage_lock = threading.RLock()
        self._pending_deps: Dict[TaskID, Tuple[TaskSpec, set]] = {}
        self._obj_waiters: Dict[ObjectID, List[TaskID]] = {}
        self._deps_lock = threading.Lock()
        self._ready: "queue.Queue" = queue.Queue()
        self._running: Dict[TaskID, TaskContext] = {}
        self._cancelled: set = set()
        self._generators: Dict[TaskID, ObjectRefGenerator] = {}
        #: Tasks submitted but not yet finished/failed — lets get() tell
        #: "still computing" apart from "object lost, reconstruct from lineage".
        self._inflight: set = set()

        # Actors.
        self._actors: Dict[ActorID, _ActorState] = {}
        self._named_actors: Dict[Tuple[str, str], ActorID] = {}
        self._actors_lock = threading.Lock()

        # Task events for the state API (ref: gcs_task_manager.h:86).
        self.task_events: deque = deque(maxlen=self.config.max_task_events)

        # Execution pool for the thread tier; resource accounting does the
        # real concurrency limiting, this is just a thread cache.
        self._exec_pool = _LeanExecPool(
            max_threads=512, name="ray_tpu_worker"
        )
        self._dispatcher_stop = threading.Event()
        self._blocked_count = 0
        self._retry_pending = False
        self.scheduler.on_release = self._notify_resources_freed
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="ray_tpu_dispatcher", daemon=True
        )
        self._dispatcher.start()
        self.start_time = time.time()

    # ------------------------------------------------------------------ events
    def _emit_event(self, task_id: TaskID, name: str, state: str, **extra) -> None:
        # deque.append is GIL-atomic — no lock on the hot path (3 events per
        # task at task-throughput rates); list_task_events' list(deque) is
        # likewise safe against concurrent appends.
        self.task_events.append(
            {"task_id": str(task_id), "name": name, "state": state,
             "time": time.time(), **extra}
        )
        if state in ("FINISHED", "FAILED"):
            metrics_agent.record_task_finished(state == "FINISHED")

    # ------------------------------------------------------------------- puts
    def put(self, value: Any, _owner: str = "driver") -> ObjectRef:
        if isinstance(value, ObjectRef):
            raise TypeError("Calling put() on an ObjectRef is not allowed.")
        object_id = ObjectID.from_put(put_counter.next(), self.worker_id[:8])
        self.store.put(object_id, value, owner=_owner)
        return ObjectRef(object_id, owner=_owner)

    # ----------------------------------------------------------- OOM defense
    def _track_leased_worker(self, worker, retriable: bool) -> None:
        """Register a busy process worker as an OOM-kill candidate
        (ref: raylet worker_killing_policy — the monitor picks victims among
        running workers, retriable-first/newest-first)."""
        entry = _LeasedWorker(worker, retriable)
        with self._leased_lock:
            self._leased_workers[id(worker)] = entry
        self._maybe_start_memory_monitor()

    def _untrack_leased_worker(self, worker) -> None:
        with self._leased_lock:
            self._leased_workers.pop(id(worker), None)

    def _maybe_start_memory_monitor(self) -> None:
        if self._memory_monitor is not None \
                or self.config.memory_monitor_threshold >= 1.0:
            return
        from ray_tpu._private.memory_monitor import MemoryMonitor

        def victims():
            with self._leased_lock:
                return list(self._leased_workers.values())

        def kill(lw):
            # Re-check ENTRY IDENTITY under the lock: the task may have
            # finished and the worker been re-leased to a new (possibly
            # non-retriable) task between the monitor's snapshot and this
            # kill — a same-id fresh entry means the victim is gone.
            with self._leased_lock:
                if self._leased_workers.get(id(lw.worker)) is not lw:
                    return
                lw.worker.kill()

        self._memory_monitor = MemoryMonitor(
            victims_fn=victims, kill_fn=kill,
            threshold=self.config.memory_monitor_threshold,
            check_interval_s=self.config.memory_monitor_interval_s,
            min_memory_free_bytes=(
                self.config.memory_monitor_min_free_bytes or None))
        self._memory_monitor.start()

    # --------------------------------------------------- cluster introspection
    # Uniform surface shared with ClientRuntime so the public API never has
    # to reach into `.scheduler` / private state (ray:// proxies these).
    def cluster_resources(self) -> Dict[str, float]:
        return self.scheduler.cluster_resources()

    def available_resources(self) -> Dict[str, float]:
        return self.scheduler.available_resources()

    def nodes(self) -> List[dict]:
        return [n.snapshot() for n in self.scheduler.nodes()]

    def list_task_events(self) -> List[dict]:
        # Appends are lock-free (see _emit_event); list(deque) can raise if
        # a GC-triggered thread switch lands an append mid-copy — retry,
        # backing off so the appenders drain.  Never fabricate emptiness:
        # an operator debugging an overload must not see zero tasks.
        for attempt in range(64):
            try:
                return list(self.task_events)
            except RuntimeError:
                if attempt > 8:
                    time.sleep(0.001)
        raise RuntimeError(
            "task-event snapshot kept colliding with concurrent appends")

    # --------------------------------------------------------- object plane
    def start_object_server(self) -> str:
        """Start (idempotently) the node object server; returns host:port."""
        from ray_tpu._private import object_transfer

        if self.object_server is None:
            self.object_server = object_transfer.ObjectTransferServer(
                lambda: self.store, on_received=self._on_object_ready,
                is_pending=self._object_is_pending,
                on_borrow=self._on_remote_borrow,
                on_borrow_release=self._on_remote_borrow_release,
                on_invoke=self._cross_lang_invoke,
                may_free=lambda oid: (
                    self.refcounter.count(oid) == 0
                    and not self._borrow_ledger().is_borrowed(oid)),
                on_borrower_lost=self._on_borrower_lost,
                host=self.config.object_transfer_host)
        self._pull_manager()  # pulls and serves share a lifetime
        return self.object_server.addr

    # ---------------------------------------------------- cross-language
    def register_cross_lang(self, name: str, fn) -> None:
        """Publish `fn` for name-based invocation by non-Python clients
        over the object plane (OP_INVOKE; the registry model of the
        reference's cross-language calls — a C++ caller cannot produce a
        Python closure, so the driver registers the callable).  `fn`
        receives the caller's raw bytes payload and should return bytes
        (the shape the C++ client's pickle codec speaks)."""
        self._cross_lang_fns[name] = fn

    def _cross_lang_invoke(self, name: str, payload: bytes) -> str:
        fn = self._cross_lang_fns.get(name)
        if fn is None:
            raise KeyError(name)
        import ray_tpu

        ref = ray_tpu.remote(fn).remote(payload)
        # Pin: the driver drops its reference immediately, but the foreign
        # caller still has to pull the result — keep a bounded window of
        # recent results alive (the caller cannot participate in the
        # borrower protocol).
        self._cross_lang_results.append(ref)
        return str(ref.id)

    # Borrowing protocol (owner side) — a borrowed object survives the local
    # refcount hitting zero until every borrower releases
    # (ref: reference_count.h:66 borrower bookkeeping).
    def _borrow_ledger(self):
        return self._borrows

    def _on_remote_borrow(self, object_id: ObjectID, borrower: str) -> None:
        self._borrow_ledger().add(object_id, borrower)

    def _on_remote_borrow_release(self, object_id: ObjectID, borrower: str) -> None:
        if self._borrow_ledger().release(object_id, borrower) \
                and self.refcounter.count(object_id) == 0:
            # Last borrower gone and no local handles: free now (the local
            # zero-callback already fired and deferred to the borrow).
            self._on_zero_refs(object_id)

    def _on_borrower_lost(self, borrower_id: str) -> None:
        """A borrower process died without releasing (its liveness session
        hit EOF): reap every borrow it held; objects whose LAST holder it
        was — and with no local handles — free now (ref:
        reference_count.h worker-death reclamation)."""
        for object_id in self._borrow_ledger().drop_borrower(borrower_id):
            if self.refcounter.count(object_id) == 0:
                self._on_zero_refs(object_id)

    def _object_is_pending(self, object_id: ObjectID) -> bool:
        """Owner-side directory answer: is something still producing this
        object (so a remote pull should wait instead of declaring loss)?"""
        task_id = object_id.task_id()
        if task_id in self._inflight:
            return True
        with self._lineage_lock:
            return object_id in self._lineage

    def owns_object(self, object_id: ObjectID) -> bool:
        """Is this process the object's owner (holder or producer)?  Used to
        decide whether refs leaving here may claim our server address —
        forwarding someone else's ref must not claim ownership."""
        return self.store.state_of(object_id) is not None \
            or self._object_is_pending(object_id)

    def _pull_manager(self):
        from ray_tpu._private import object_transfer

        if self._pull_mgr is None:
            self._pull_mgr = object_transfer.PullManager(
                self.store, on_complete=self._on_object_ready,
                on_failure=self._on_pull_failed,
                is_live=lambda oid: self.refcounter.count(oid) > 0)
        return self._pull_mgr

    def _on_pull_failed(self, object_id: ObjectID, msg: str) -> None:
        """Terminal failure of a dependency pull: poison the store entry so
        tasks parked on it dispatch, observe the error while resolving args,
        and fail instead of hanging (the object may still be re-created by
        lineage or a later successful pull overwriting nothing — the entry is
        already FAILED and get() raises)."""
        from ray_tpu._private.object_transfer import ObjectTransferError

        if not self.store.contains(object_id):
            self.store.put_error(object_id, ObjectTransferError(msg))
            self._on_object_ready(object_id)

    def _remote_owner_addr(self, ref: ObjectRef) -> str:
        """The address to pull a ref from, or "" if it is locally owned.

        The location table wins over the ref's stamped owner address: it is
        head-authoritative and survives reconstruction onto a different
        node, whereas the stamp is frozen at serialization time."""
        addr = self.location_of(ref.id) or getattr(ref, "owner_addr", "")
        if not addr:
            return ""
        if self.object_server is not None and addr == self.object_server.addr:
            return ""
        return addr

    # ------------------------------------------------------- worker nodes
    # Head side of cross-host execution (ref: node_manager.h:117,
    # cluster_task_manager.h:42 spillback, gcs_node_manager.h registration).
    def start_node_server(self, host: str = "127.0.0.1", port: int = 0) -> str:
        """Start (idempotently) the head's node-manager service; worker
        nodes join it via ``ray_tpu worker --address=<returned addr>``."""
        from ray_tpu._private.node_manager import NodeManagerServer

        if self.node_server is None:
            self.start_object_server()  # results/args ride the object plane
            self.node_server = NodeManagerServer(self, host=host, port=port)
        return self.node_server.address

    def location_of(self, object_id: ObjectID) -> str:
        """Object-plane address of the node holding a result that stayed
        remote ("" if unknown/local)."""
        with self._locations_lock:
            return self._object_locations.get(object_id, "")

    def _register_remote_node(self, node, info: dict) -> bool:
        """Returns True when this is a FRESH registration — the head holds
        no state for the node (first join, or loss recovery already ran and
        dropped it).  A re-register of a still-known node (transient
        reconnect that beat the loss handler) keeps the head's scheduler
        ledger so in-flight leases aren't double-counted."""
        resources = dict(info.get("resources") or {})
        labels = dict(info.get("labels") or {})
        labels.setdefault("node-ip", node.conn._sock.getpeername()[0]
                          if hasattr(node.conn, "_sock") else "")
        with self._remote_nodes_lock:
            fresh = node.node_id not in self._remote_nodes
            self._remote_nodes[node.node_id] = node
        existing = self.scheduler.get_node(node.node_id)
        if fresh or existing is None or not existing.alive:
            self.scheduler.add_node(resources, labels, node_id=node.node_id)
            fresh = True
        return fresh

    def _remote_nodes_snapshot(self) -> List:
        with self._remote_nodes_lock:
            return list(self._remote_nodes.values())

    def _remote_node(self, node_id: NodeID):
        with self._remote_nodes_lock:
            return self._remote_nodes.get(node_id)

    def _dispatch_remote(self, spec: TaskSpec, node_id: NodeID, release) -> None:
        """Ship a leased task to its node; completion frames finish it."""
        node = self._remote_node(node_id)
        if node is None or not node.alive:
            release()
            self._handle_task_failure(
                spec, WorkerCrashedError(f"node {node_id} vanished before dispatch"))
            return
        self._emit_event(spec.task_id, spec.name, "SUBMITTED_TO_WORKER",
                         node_id=str(node_id))
        with self._remote_lock:
            self._remote_inflight[spec.task_id] = (spec, release, node_id)
        try:
            node.conn.send(("task", serialization.dumps_inband(spec)))
        except (OSError, ConnectionError):
            with self._remote_lock:
                self._remote_inflight.pop(spec.task_id, None)
            release()
            # The node is gone: run loss recovery NOW so the retry below
            # (and every other blocked task) stops leasing its resources.
            self._declare_node_lost(node)
            self._handle_task_failure(
                spec, WorkerCrashedError(f"node {node_id} unreachable"))
        except BaseException as e:  # noqa: BLE001 — e.g. unpicklable func
            with self._remote_lock:
                self._remote_inflight.pop(spec.task_id, None)
            release()
            self._fail_task(spec, e, retry=False)

    def _land_remote_result(self, object_id: ObjectID, item: Tuple, node) -> None:
        kind, payload = item
        if kind == "inline":
            if not self.store.contains(object_id):
                self.store.put_serialized(object_id, payload,
                                          owner=str(node.node_id))
        else:  # "stored": primary copy stays on the producer
            with self._locations_lock:
                self._object_locations[object_id] = payload
        self._on_object_ready(object_id)

    def _on_remote_task_done(self, node, task_id: TaskID, results: List[Tuple]) -> None:
        with self._remote_lock:
            entry = self._remote_inflight.pop(task_id, None)
        if entry is None:
            return  # node-loss handling or cancel already settled it
        spec, release, _ = entry
        release()
        if spec.generator:
            gen = self._generators.pop(task_id, None)
            if results and results[0][0] == "error":
                err = serialization.loads(results[0][1])
                self._generators[task_id] = gen  # _fail_task pops + finishes
                self._handle_task_failure(spec, err)
                return
            if gen is not None:
                gen._finish()
            self._inflight.discard(task_id)
            self._emit_event(task_id, spec.name, "FINISHED")
            return
        errors = [r for r in results if r[0] == "error"]
        if errors:
            err = serialization.loads(errors[0][1])
            self._handle_task_failure(spec, err)
            return
        for i, item in enumerate(results):
            self._land_remote_result(
                ObjectID.for_task_return(spec.task_id, i), item, node)
        self._inflight.discard(task_id)
        self._emit_event(task_id, spec.name, "FINISHED")

    def _on_remote_task_yield(self, node, task_id: TaskID, index: int,
                              item: Tuple) -> None:
        object_id = ObjectID.for_task_return(task_id, index)
        if item[0] == "error":
            err = serialization.loads(item[1])
            if not isinstance(err, (TaskError, ObjectLostError)):
                err = TaskError(err, task_repr=str(task_id))
            self.store.put_error(object_id, err)
            self._on_object_ready(object_id)
        else:
            self._land_remote_result(object_id, item, node)
        gen = self._generators.get(task_id)
        if gen is not None:
            gen._push(ObjectRef(object_id, owner=str(node.node_id)))

    def _on_remote_actor_ready(self, node, actor_id: ActorID) -> None:
        state = self._actors.get(actor_id)
        if state is None:
            return
        state.state = _ActorState.ALIVE
        state.ready_event.set()
        if not state.threads:
            self._start_actor_executors(state)

    def _on_remote_actor_dead(self, node, actor_id: ActorID,
                              err: BaseException) -> None:
        """The node reports the actor terminally dead (creation failure or
        its local FSM exhausted restarts) — mirror local death handling."""
        state = self._actors.get(actor_id)
        if state is None:
            return
        with state.lock:
            state.remote_node = None
            if state.release is not None:
                state.release()
                state.release = None
            if not isinstance(err, ActorDiedError):
                err = ActorDiedError(cause=err)
            state.death_cause = err
            state.state = _ActorState.DEAD
            with self._actors_lock:
                key = (state.spec.namespace, state.spec.name)
                if state.spec.name and self._named_actors.get(key) == actor_id:
                    del self._named_actors[key]
            for _ in state.threads:
                state.mailbox.put(None)
        state.ready_event.set()
        self._drain_mailbox(state)

    def _declare_node_lost(self, node) -> None:
        """Idempotent entry to node-death recovery: a failed send, the
        reader's EOF and the heartbeat monitor all race to report it, but
        recovery — and especially removing the node from the scheduler so
        retries stop re-leasing it — must run exactly once, and EARLY (a
        retry burning its whole budget on a dead-but-still-registered node
        is the failure mode this guards)."""
        with self._remote_nodes_lock:
            if node.lost_handled:
                return
            node.lost_handled = True
        node.alive = False
        try:
            node.conn.close()
        except Exception:
            pass
        self._on_node_lost(node)

    def _on_node_lost(self, node) -> None:
        """Connection loss / missed heartbeats: remove the node, retry its
        tasks, restart its actors, reconstruct its objects (ref:
        gcs_health_check_manager.h:45, object_recovery_manager.h:38)."""
        node_id = node.node_id
        with self._remote_nodes_lock:
            superseded = self._remote_nodes.get(node_id) is not node
            if not superseded:
                self._remote_nodes.pop(node_id, None)
                # Inside the lock: a rejoin that re-registers between the
                # pop and this removal would have its fresh scheduler entry
                # deleted out from under it (register takes this lock too).
                self.scheduler.remove_node(node_id)
        if superseded:
            # The node already RE-REGISTERED over a fresh connection (rejoin
            # races this loss handler): the process is alive, its dispatched
            # work keeps running and reports over the NEW connection —
            # removing it from the registry/scheduler or restarting its
            # actors here would silently wreck a live, rejoined node.
            return

        with self._remote_lock:
            lost = [(tid, e) for tid, e in self._remote_inflight.items()
                    if e[2] == node_id]
            for tid, _ in lost:
                del self._remote_inflight[tid]
        for _tid, (spec, release, _) in lost:
            release()
            if spec.actor_id is not None:
                self._fail_task(spec, ActorDiedError(
                    f"node {node_id} died mid-call"), retry=False)
            else:
                self._handle_task_failure(
                    spec, WorkerCrashedError(f"node {node_id} died"))

        with self._locations_lock:
            lost_oids = [oid for oid, addr in self._object_locations.items()
                         if addr == node.object_addr]
            for oid in lost_oids:
                del self._object_locations[oid]
        for oid in lost_oids:
            if self.store.contains(oid):
                continue
            spec = self._lineage_for(oid)
            if spec is not None and oid.task_id() not in self._inflight:
                self._resubmit(spec)
            elif spec is None:
                self.store.put_error(oid, ObjectLostError(
                    f"object {oid} lost with node {node_id}"))
                self._on_object_ready(oid)

        with self._actors_lock:
            states = list(self._actors.values())
        for state in states:
            if state.remote_node == node_id:
                state.remote_node = None  # node gone; no kill frame to send
                self._kill_actor_state(state, ActorDiedError(
                    f"node {node_id} died"), no_restart=False)

    def _release_export(self, object_id: ObjectID, addr: str) -> None:
        """Async-release a producer's export pin (we were the last holder).
        Runs off-thread: this is reached from GC (`__del__`), which must
        never block on TCP."""
        if self._export_release_q is None:
            q: "queue.SimpleQueue" = queue.SimpleQueue()

            def _drain():
                from ray_tpu._private.borrowing import _send_borrow_op
                from ray_tpu._private.node_manager import EXPORT_BORROWER

                while True:
                    oid, a = q.get()
                    _send_borrow_op("release", oid, a, EXPORT_BORROWER)

            self._export_release_q = q
            threading.Thread(target=_drain, name="ray_tpu_export_release",
                             daemon=True).start()
        self._export_release_q.put((object_id, addr))

    # ------------------------------------------------------------------- gets
    def get(self, refs: Any, timeout: Optional[float] = None) -> Any:
        single = isinstance(refs, ObjectRef)
        ref_list = [refs] if single else list(refs)
        for r in ref_list:
            if not isinstance(r, ObjectRef):
                raise TypeError(f"get() expects ObjectRef(s), got {type(r)}")
        # Vectorized fast path: one store pass resolves every ref whose
        # value is already local (the 10k-object get anchor); only the
        # stragglers take the per-ref slow path (pulls, reconstruction,
        # inflight waits).
        values, missing = self.store.try_get_many([r.id for r in ref_list])
        if not missing:
            return values[0] if single else values
        ctx = current_task_context()
        released = False
        if ctx is not None and ctx.lease_release is not None:
            # Release this task's resources while blocked (the reference
            # releases CPU while a worker blocks in ray.get).
            ctx.lease_release()
            released = True
        try:
            for i in missing:
                values[i] = self._get_one(ref_list[i], timeout)
        finally:
            if released:
                ctx.lease_reacquire()
        return values[0] if single else values

    def _get_one(self, ref: ObjectRef, timeout: Optional[float]) -> Any:
        # One deadline governs the whole get: remote pulls, inflight waits
        # and the store materialization all share it, so get(timeout=T)
        # blocks at most ~T, not a multiple (ADVICE r2).
        deadline = None if timeout is None else time.monotonic() + timeout

        def _remaining() -> Optional[float]:
            return None if deadline is None \
                else max(0.0, deadline - time.monotonic())

        reconstructs = 0
        while True:
            if self.store.contains(ref.id):
                try:
                    return self.store.get(ref.id, _remaining())
                except ObjectLostError:
                    spec = self._lineage_for(ref.id)
                    reconstructs += 1
                    if spec is None or reconstructs > 3:
                        raise
                    # Drop the poisoned/freed entry so the loop waits for
                    # the reconstruction instead of re-reading the error.
                    self.store.free(ref.id)
                    self._resubmit(spec)
                    continue
            addr = self._remote_owner_addr(ref)
            if addr:
                # Remote copy exists (owner-stamped or location table):
                # pull it (ref: pull_manager.h:52).  A lost holder falls
                # back to lineage reconstruction.
                try:
                    self._pull_manager().pull_blocking(ref.id, addr, _remaining())
                except GetTimeoutError:
                    raise
                except ObjectLostError:
                    with self._locations_lock:  # the holder lied or died
                        self._object_locations.pop(ref.id, None)
                    if ref.id.task_id() in self._inflight:
                        # A reconstruction is already running; wait for it.
                        self._wait_value_or_location(ref.id, _remaining())
                        continue
                    spec = self._lineage_for(ref.id)
                    reconstructs += 1
                    if spec is None or reconstructs > 3:
                        raise
                    self._resubmit(spec)
                continue
            task_id = ref.id.task_id()
            if task_id in self._inflight:
                # Still computing (here or on a worker node): wait for a
                # local value/error OR a remote location to appear.
                self._wait_value_or_location(ref.id, _remaining())
                continue
            # Not in flight, no local value, no known copy: lost — try
            # lineage (ref: object_recovery_manager.h:38).
            spec = self._lineage_for(ref.id)
            if spec is not None:
                self._resubmit(spec)
                continue
            return self.store.get(ref.id, _remaining())

    def _wait_value_or_location(self, object_id: ObjectID,
                                timeout: Optional[float]) -> None:
        """Block until the object resolves locally (value/error) or a
        worker node reports it produced-and-stored (location table).
        Event-driven: every completion path funnels through
        _on_object_ready, which fires the registered event."""
        if self.store.contains(object_id) or self.location_of(object_id):
            return
        with self._locations_lock:
            ev = self._ready_events.get(object_id)
            if ev is None:
                ev = self._ready_events[object_id] = threading.Event()
        try:
            # Re-check AFTER registering: a completion between the first
            # check and the registration would otherwise be missed.
            if self.store.contains(object_id) or self.location_of(object_id):
                return
            if not ev.wait(timeout):
                raise GetTimeoutError(
                    f"Timed out waiting for object {object_id}")
        finally:
            with self._locations_lock:
                if self._ready_events.get(object_id) is ev and ev.is_set():
                    del self._ready_events[object_id]

    async def get_async(self, ref: ObjectRef) -> Any:
        loop = asyncio.get_event_loop()
        return await loop.run_in_executor(None, self._get_one, ref, None)

    def as_future(self, ref: ObjectRef) -> concurrent.futures.Future:
        fut: concurrent.futures.Future = concurrent.futures.Future()

        def _resolve():
            try:
                fut.set_result(self._get_one(ref, None))
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)

        threading.Thread(target=_resolve, daemon=True).start()
        return fut

    def wait(self, refs: Sequence[ObjectRef], num_returns: int = 1,
             timeout: Optional[float] = None, fetch_local: bool = True):
        if not refs:
            return [], []
        refs = list(refs)
        if num_returns > len(refs):
            raise ValueError("num_returns exceeds number of refs")
        if fetch_local:
            for r in refs:
                addr = self._remote_owner_addr(r)
                if addr and not self.store.contains(r.id):
                    self._pull_manager().request(r.id, addr)
        deadline = None if timeout is None else time.monotonic() + timeout
        ready: List[ObjectRef] = []
        pending = list(refs)
        requested: set = set()
        while len(ready) < num_returns:
            progressed = False
            for r in list(pending):
                is_ready = self.store.contains(r.id)
                if not is_ready:
                    loc = self.location_of(r.id)
                    if loc:
                        if fetch_local:
                            # Produced on a worker node mid-wait: start the
                            # pull; ready once it lands.
                            if r.id not in requested:
                                requested.add(r.id)
                                self._pull_manager().request(r.id, loc)
                        else:
                            # fetch_local=False: existing anywhere counts.
                            is_ready = True
                if is_ready:
                    ready.append(r)
                    pending.remove(r)
                    progressed = True
                    if len(ready) >= num_returns:
                        break
            if len(ready) >= num_returns:
                break
            if deadline is not None and time.monotonic() >= deadline:
                break
            if not progressed:
                remaining = 0.01 if deadline is None else min(0.01, deadline - time.monotonic())
                if pending and remaining > 0:
                    self.store.wait_ready(pending[0].id, remaining)
                elif remaining <= 0:
                    break
        return ready, pending

    # ---------------------------------------------------------------- submits
    def submit_task(self, spec: TaskSpec) -> Any:
        if tracing.is_tracing_enabled():
            with tracing.span(f"submit::{spec.name}",
                              attributes={"task_id": spec.task_id}):
                tracing.inject_task_spec(spec)
                return self._submit_task_inner(spec)
        return self._submit_task_inner(spec)

    def _submit_task_inner(self, spec: TaskSpec) -> Any:
        # Batched ownership bookkeeping: one refcounter pass for all return
        # handles instead of one lock round-trip per ref.
        oids = [ObjectID.for_task_return(spec.task_id, i)
                for i in range(spec.num_returns)]
        self.refcounter.add_many(oids)
        refs = [ObjectRef(oid, owner=self.worker_id, _add_ref=False)
                for oid in oids]
        with self._lineage_lock:
            for ref in refs:
                self._lineage[ref.id] = spec
        gen = None
        if spec.generator:
            gen = ObjectRefGenerator(spec.task_id)
            self._generators[spec.task_id] = gen
        self._emit_event(spec.task_id, spec.name, "PENDING_ARGS_AVAIL")
        self._inflight.add(spec.task_id)
        self._enqueue_after_deps(spec)
        if spec.generator:
            return gen
        return refs[0] if spec.num_returns == 1 else refs

    def _enqueue_after_deps(self, spec: TaskSpec) -> None:
        ref_args = [a for a in list(spec.args) + list(spec.kwargs.values())
                    if isinstance(a, ObjectRef)]
        if not ref_args:
            self._ready.put(spec)
            return
        deps = set()
        present = self.store.contains_many([a.id for a in ref_args])
        for a, here in zip(ref_args, present):
            if not here:
                if self.location_of(a.id):
                    # Produced, held by a worker node: the EXECUTING side
                    # pulls it on demand (it may be dispatched right back
                    # to the holder — prefetching here would drag every
                    # block through the head).
                    continue
                deps.add(a.id)
                addr = self._remote_owner_addr(a)
                if addr:
                    # Remote-owned dependency: start pulling now so the task
                    # unblocks when the transfer lands (the reference's
                    # DependencyManager subscribes+pulls args the same way).
                    self._pull_manager().request(a.id, addr)
        if not deps:
            self._ready.put(spec)
            return
        with self._deps_lock:
            dep_list = list(deps)
            landed = self.store.contains_many(dep_list)
            still = {d for d, here in zip(dep_list, landed) if not here}
            if not still:
                self._ready.put(spec)
                return
            self._pending_deps[spec.task_id] = (spec, still)
            for d in still:
                self._obj_waiters.setdefault(d, []).append(spec.task_id)

    def _on_object_ready(self, object_id: ObjectID) -> None:
        with self._locations_lock:
            ev = self._ready_events.pop(object_id, None)
        if ev is not None:
            ev.set()
        to_ready = []
        with self._deps_lock:
            for task_id in self._obj_waiters.pop(object_id, []):
                entry = self._pending_deps.get(task_id)
                if entry is None:
                    continue
                spec, deps = entry
                deps.discard(object_id)
                if not deps:
                    del self._pending_deps[task_id]
                    to_ready.append(spec)
        for spec in to_ready:
            self._ready.put(spec)

    def _resubmit(self, spec: TaskSpec) -> None:
        spec.attempt += 1
        self._emit_event(spec.task_id, spec.name, "RESUBMITTED", attempt=spec.attempt)
        self._inflight.add(spec.task_id)
        if spec.actor_id is not None:
            state = self._actors.get(spec.actor_id)
            if state is not None and state.state != _ActorState.DEAD:
                state.mailbox.put(spec)
                return
            self._fail_task(spec, ActorDiedError("actor gone; cannot reconstruct"), retry=False)
            return
        self._enqueue_after_deps(spec)

    # -------------------------------------------------------------- dispatch
    def _notify_resources_freed(self) -> None:
        """Scheduler release hook: wake the dispatcher to retry blocked tasks.

        Coalesced — at most one retry token is in the queue at a time, so a
        burst of releases costs one blocked-list scan, not one per release
        (the old retry-on-every-queue-event design degraded O(blocked x
        events): 16.7 _try_dispatch calls per task in bench_core)."""
        if self._blocked_count and not self._retry_pending:
            self._retry_pending = True
            self._ready.put(_RETRY_BLOCKED)

    @staticmethod
    def _placement_shape(spec: TaskSpec) -> tuple:
        """Bucket key under which blocked specs are interchangeable for
        placement feasibility: same resource demand + same strategy
        semantics.  Stateless strategies collapse into one bucket per
        demand shape; parameterized strategies (affinity, labels, PGs)
        bucket per instance — correct, and they are never the 1M-task
        storm case."""
        res = tuple(sorted(spec.resources.items())) if spec.resources else ()
        strat = spec.strategy
        if strat is None or type(strat) is DefaultStrategy:
            return (res, "DEFAULT")
        if type(strat) is SpreadStrategy:
            return (res, "SPREAD")
        return (res, id(strat))

    def _dispatch_loop(self) -> None:
        # Blocked tasks live in per-placement-shape FIFO queues: a capacity
        # event probes one head per shape instead of rescanning every
        # blocked spec.  The old flat list retried O(blocked) specs per
        # release and removed with O(blocked) list scans — quadratic once
        # a 1M-task backlog forms behind a busy cluster; this is
        # O(shapes + dispatched) per release.
        blocked: Dict[tuple, deque] = {}
        blocked_n = 0

        def retry_blocked() -> None:
            nonlocal blocked_n
            for key in list(blocked):
                q = blocked.get(key)
                while q:
                    if self._try_dispatch(q[0]):
                        q.popleft()
                        blocked_n -= 1
                    else:
                        break  # shape doesn't fit now; next bucket
                if not q:
                    blocked.pop(key, None)
            self._blocked_count = blocked_n

        while not self._dispatcher_stop.is_set():
            try:
                spec = self._ready.get(timeout=0.2)
            except queue.Empty:
                # Safety net for release notifications racing the flag.
                if blocked:
                    retry_blocked()
                continue
            if spec is None:
                break
            if spec is _RETRY_BLOCKED:
                self._retry_pending = False
                retry_blocked()
                continue
            key = self._placement_shape(spec)
            q = blocked.get(key)
            if q:
                # FIFO fairness: same-shape work already waits; dispatching
                # around it would starve the backlog's head forever.  Still
                # report demand — the autoscaler sizes off the full backlog,
                # not one probe per shape.
                self.scheduler.report_task_demand(spec.task_id, spec.resources)
                q.append(spec)
                blocked_n += 1
                self._blocked_count = blocked_n
            elif not self._try_dispatch(spec):
                blocked.setdefault(key, deque()).append(spec)
                blocked_n += 1
                self._blocked_count = blocked_n

    def _try_dispatch(self, spec: TaskSpec) -> bool:
        if spec.task_id in self._cancelled:
            self.scheduler.clear_task_demand(spec.task_id)
            self._fail_task(spec, TaskCancelledError(str(spec.task_id)), retry=False)
            return True
        lease = self.scheduler.try_acquire(spec.resources, spec.strategy)
        if lease is None:
            # Infeasible requests fail fast instead of hanging forever —
            # unless an autoscaler is running, which may add capacity.
            from ray_tpu._private.scheduling import DefaultStrategy

            strategy = spec.strategy or DefaultStrategy()
            with self.scheduler._lock:
                feasible = self.scheduler._feasible_anywhere_locked(spec.resources, strategy)
            # (feasibility counts launchable autoscaler node types, so this
            # is a genuine never-fits even with autoscaling on.)
            if not feasible and not isinstance(strategy, PlacementGroupSchedulingStrategy):
                from ray_tpu._private.scheduling import InfeasibleError

                # Drop any demand reported on an earlier blocked pass, or a
                # running autoscaler keeps launching nodes for a dead task.
                self.scheduler.clear_task_demand(spec.task_id)
                self._fail_task(
                    spec,
                    InfeasibleError(
                        f"Task {spec.name} requests {spec.resources} which no node can "
                        f"ever satisfy (cluster total: {self.scheduler.cluster_resources()})"
                    ),
                    retry=False,
                )
                return True
            # Blocked: visible to the autoscaler as unmet demand.
            self.scheduler.report_task_demand(spec.task_id, spec.resources)
            return False
        self.scheduler.clear_task_demand(spec.task_id)
        node_id, release = lease
        if node_id in self._remote_nodes:
            # Placed on a joined worker node: ship the spec over its
            # connection (ref: cluster_task_manager.h spillback — here the
            # grant itself lands on the remote node's resources).
            self._dispatch_remote(spec, node_id, release)
            return True
        self._emit_event(spec.task_id, spec.name, "SUBMITTED_TO_WORKER", node_id=str(node_id))
        try:
            self._exec_pool.submit(self._execute_task, spec, node_id, release)
        except RuntimeError:
            release()
            self._fail_task(spec, WorkerCrashedError("runtime is shutting down"),
                            retry=False)
        return True

    # -------------------------------------------------------------- execution
    def _execute_task(self, spec: TaskSpec, node_id: NodeID, release) -> None:
        reacquire_box = {"release": release}

        def lease_release():
            reacquire_box["release"]()

        def lease_reacquire():
            _, new_release = self.scheduler.acquire(spec.resources, spec.strategy)
            reacquire_box["release"] = new_release

        ctx = TaskContext(spec.task_id, spec.actor_id, lease_release, lease_reacquire)
        self._running[spec.task_id] = ctx
        _task_ctx.ctx = ctx
        self._emit_event(spec.task_id, spec.name, "RUNNING")
        try:
            with tracing.task_execute_span(spec):
                if self._chaos:
                    from ray_tpu._private import fault_injection

                    fault_injection.check("execute")
                args, kwargs = self._resolve_args(spec)
                if spec.isolation == "process" or spec.runtime_env:
                    # A runtime env implies the process tier: envs are
                    # per-worker-process state (ref: worker_pool.h env-keyed
                    # workers); thread-tier tasks share the driver process.
                    if spec.generator:
                        self._run_generator_in_process(spec, args, kwargs)
                        result = None
                    else:
                        result = self._run_in_process(spec, args, kwargs)
                elif spec.generator:
                    self._run_generator(spec, args, kwargs)
                    result = None
                else:
                    result = spec.func(*args, **kwargs)
            if spec.task_id in self._cancelled:
                raise TaskCancelledError(str(spec.task_id))
            if not spec.generator:
                self._store_results(spec, result)
            self._emit_event(spec.task_id, spec.name, "FINISHED")
        except BaseException as e:  # noqa: BLE001
            self._handle_task_failure(spec, e)
        finally:
            _task_ctx.ctx = None
            self._running.pop(spec.task_id, None)
            reacquire_box["release"]()

    def _resolve_ref(self, v: Any) -> Any:
        """Arg materialization shared by task and actor paths: local store
        hit, else _get_one (object-plane pull + lineage reconstruction)."""
        if not isinstance(v, ObjectRef):
            return v
        if self.store.contains(v.id):
            return self.store.get(v.id)
        return self._get_one(v, None)

    def _resolve_args(self, spec: TaskSpec):
        args = spec.args
        kwargs = spec.kwargs
        ref_idx = [i for i, a in enumerate(args) if isinstance(a, ObjectRef)]
        if ref_idx:
            # One store pass for every ref arg (a 10k-arg call would
            # otherwise pay two lock round-trips per ref); stragglers take
            # the pull/reconstruction slow path individually.
            vals, missing = self.store.try_get_many(
                [args[i].id for i in ref_idx])
            resolved = dict(zip(ref_idx, vals))
            for j in missing:
                i = ref_idx[j]
                resolved[i] = self._resolve_ref(args[i])
            args = tuple(resolved.get(i, a) if isinstance(a, ObjectRef) else a
                         for i, a in enumerate(args))
        else:
            args = tuple(args)
        kwargs = {k: self._resolve_ref(v) for k, v in kwargs.items()}
        return args, kwargs

    def _lease_env_worker(self, spec: TaskSpec):
        """Stage the spec's runtime env (if any) and lease a matching
        process worker; returns (worker, fn_id, fn_bytes)."""
        fn = spec.func
        fn_id = getattr(fn, "__qualname__", "fn") + ":" + str(id(fn))
        fn_bytes = serialization.dumps(fn)
        env_key, env_payload = "", None
        if spec.runtime_env:
            from ray_tpu._private.runtime_env import RuntimeEnv, payload_key

            env = RuntimeEnv.normalize(spec.runtime_env)
            env_payload = env.stage()
            env_key = payload_key(env_payload)
        return self.process_pool.lease(env_key, env_payload), fn_id, fn_bytes

    def _run_in_process(self, spec: TaskSpec, args, kwargs):
        if self._chaos:
            from ray_tpu._private import fault_injection

            fault_injection.check("process_exec")
        worker, fn_id, fn_bytes = self._lease_env_worker(spec)
        self._track_leased_worker(worker, retriable=spec.max_retries > 0)
        try:
            result = worker.execute(fn_id, fn_bytes, args, kwargs)
        except (TaskError, WorkerCrashedError):
            self.process_pool.discard(worker)
            raise
        finally:
            self._untrack_leased_worker(worker)
        self.process_pool.release(worker)
        return result

    def _run_generator_in_process(self, spec: TaskSpec, args, kwargs) -> None:
        """Streaming-generator task on a leased process worker: items
        arrive over the multiplexed pipe and feed the ordinary generator
        machinery (VERDICT r2 item 8 — the process tier streams now)."""
        worker, fn_id, fn_bytes = self._lease_env_worker(spec)
        self._track_leased_worker(worker, retriable=False)
        ok = False
        try:
            self._run_generator(
                spec, args, kwargs,
                iterator=worker.execute_gen(fn_id, fn_bytes, args, kwargs))
            ok = True
        finally:
            self._untrack_leased_worker(worker)
            if ok:
                self.process_pool.release(worker)
            else:
                self.process_pool.discard(worker)

    def _run_generator(self, spec: TaskSpec, args, kwargs,
                       iterator=None) -> None:
        gen_handle = self._generators.get(spec.task_id)
        index = 0
        if iterator is None:
            iterator = spec.func(*args, **kwargs)
        try:
            for value in iterator:
                if spec.task_id in self._cancelled:
                    raise TaskCancelledError(str(spec.task_id))
                object_id = ObjectID.for_task_return(spec.task_id, index)
                self.store.put(object_id, value, owner=self.worker_id)
                self._on_object_ready(object_id)
                if gen_handle is not None:
                    gen_handle._push(ObjectRef(object_id, owner=self.worker_id))
                index += 1
            if gen_handle is not None:
                gen_handle._finish()
            self._inflight.discard(spec.task_id)
        except BaseException as e:  # noqa: BLE001
            if gen_handle is not None:
                gen_handle._finish(TaskError(e, task_repr=spec.name))
            raise
        finally:
            self._generators.pop(spec.task_id, None)

    def _store_results(self, spec: TaskSpec, result: Any) -> None:
        if spec.num_returns == 1:
            outputs = [result]
        else:
            if not isinstance(result, (tuple, list)) or len(result) != spec.num_returns:
                raise ValueError(
                    f"Task {spec.name} declared num_returns={spec.num_returns} but "
                    f"returned {type(result)}")
            outputs = list(result)
        for i, value in enumerate(outputs):
            object_id = ObjectID.for_task_return(spec.task_id, i)
            self.store.put(object_id, value, owner=self.worker_id)
            self._on_object_ready(object_id)
        self._inflight.discard(spec.task_id)

    def _handle_task_failure(self, spec: TaskSpec, error: BaseException) -> None:
        # ObjectLostError counts as a system error: a dependency's holder
        # died; the retry re-waits deps while lineage reconstructs them.
        is_app_error = not isinstance(
            error, (WorkerCrashedError, SystemError, MemoryError, ObjectLostError))
        if spec.generator:
            # Streaming tasks never retry mid-stream: the consumer's
            # generator already delivered items (and the error) — a rerun
            # would overwrite per-index returns behind refs the consumer
            # holds (the reference restarts streaming generators only
            # before any item is consumed; terminal failure is the honest
            # single-semantics here).
            self._fail_task(spec, error, retry=False)
            return
        retryable = (not is_app_error) or spec.retry_exceptions
        if isinstance(error, (TaskCancelledError,)):
            retryable = False
        if retryable and spec.attempt < spec.max_retries:
            spec.attempt += 1
            self._emit_event(spec.task_id, spec.name, "RETRYING", attempt=spec.attempt)
            self._enqueue_after_deps(spec)
            return
        self._fail_task(spec, error, retry=False)

    def _fail_task(self, spec: TaskSpec, error: BaseException, retry: bool) -> None:
        if not isinstance(error, (TaskError, TaskCancelledError, ActorDiedError)):
            error = TaskError(error, task_repr=spec.name)
        for i in range(max(spec.num_returns, 1)):
            object_id = ObjectID.for_task_return(spec.task_id, i)
            # A streaming task's return index 0 is its first yielded item:
            # one that has landed stays what it is (a consumer may not have
            # read it yet); the error reaches the stream through the handle.
            if spec.generator and self.store.contains(object_id):
                continue
            self.store.put_error(object_id, error)
            self._on_object_ready(object_id)
        gen_handle = self._generators.pop(spec.task_id, None)
        if gen_handle is not None:
            gen_handle._finish(error)
        self._inflight.discard(spec.task_id)
        self._emit_event(spec.task_id, spec.name, "FAILED", error=repr(error))

    # ---------------------------------------------------------------- cancel
    def cancel(self, ref: ObjectRef, force: bool = False) -> None:
        task_id = ref.id.task_id()
        self._cancelled.add(task_id)
        with self._remote_lock:
            remote = self._remote_inflight.get(task_id)
        if remote is not None:
            node = self._remote_node(remote[2])
            if node is not None and node.alive:
                try:
                    node.conn.send(("cancel", str(task_id), force))
                except (OSError, ConnectionError):
                    pass
            return
        ctx = self._running.get(task_id)
        if ctx is not None:
            ctx.cancelled.set()
        else:
            with self._deps_lock:
                entry = self._pending_deps.pop(task_id, None)
            if entry is not None:
                self._fail_task(entry[0], TaskCancelledError(str(task_id)), retry=False)

    # ---------------------------------------------------------------- lineage
    def _lineage_for(self, object_id: ObjectID) -> Optional[TaskSpec]:
        with self._lineage_lock:
            return self._lineage.get(object_id)

    def _on_zero_refs(self, object_id: ObjectID) -> None:
        if self._borrows is not None and self._borrows.is_borrowed(object_id):
            # Remote borrowers still hold handles: the owner keeps the
            # primary copy until the last RELEASE_BORROW arrives
            # (ref: reference_count.h — borrows keep the object pinned).
            return
        with self._locations_lock:
            loc = self._object_locations.pop(object_id, None)
        if loc:
            # The last head-side handle died: release the producing node's
            # export pin so it can free its copy (off-thread — GC path).
            self._release_export(object_id, loc)
        self.store.free(object_id)
        with self._lineage_lock:
            self._lineage.pop(object_id, None)

    # ----------------------------------------------------------------- actors
    def create_actor(self, spec: ActorSpec) -> None:
        state = _ActorState(spec)
        with self._actors_lock:
            if spec.name:
                key = (spec.namespace, spec.name)
                if key in self._named_actors:
                    existing = self._actors.get(self._named_actors[key])
                    if existing is not None and existing.state != _ActorState.DEAD:
                        raise ValueError(f"Actor name '{spec.name}' already taken")
                self._named_actors[key] = spec.actor_id
            self._actors[spec.actor_id] = state
        try:
            self._exec_pool.submit(self._start_actor, state, first=True)
        except RuntimeError:
            state.death_cause = ActorDiedError("runtime is shutting down")
            state.state = _ActorState.DEAD
            state.ready_event.set()

    def _start_actor(self, state: _ActorState, first: bool) -> None:
        spec = state.spec
        try:
            node_id, release = self.scheduler.acquire(spec.resources, spec.strategy)
        except BaseException as e:  # noqa: BLE001
            state.death_cause = e
            state.state = _ActorState.DEAD
            state.ready_event.set()
            return
        state.node_id, state.release = node_id, release
        if node_id in self._remote_nodes:
            self._start_remote_actor(state, node_id)
            return
        use_process = spec.isolation == "process" or bool(
            getattr(spec, "runtime_env", None))
        try:
            args, kwargs = self._resolve_values(spec.args, spec.kwargs)
            if use_process:
                if state.is_async:
                    raise ValueError(
                        "async actors cannot use isolation='process'")
                # Dedicated worker process hosting the instance (the
                # reference's default: one worker process per actor —
                # gcs_actor_scheduler.h leases a worker for creation).
                env_key, env_payload = "", None
                if spec.runtime_env:
                    from ray_tpu._private.runtime_env import (
                        RuntimeEnv, payload_key)

                    env = RuntimeEnv.normalize(spec.runtime_env)
                    env_payload = env.stage()
                    env_key = payload_key(env_payload)
                worker = self.process_pool.lease(env_key, env_payload)
                try:
                    worker.actor_new(serialization.dumps(spec.cls),
                                     spec.actor_id, args, kwargs)
                except BaseException:
                    self.process_pool.discard(worker)
                    raise
                state.proc_worker = worker
            else:
                # __init__ runs with an actor-scoped context so code inside
                # it (e.g. collective rank binding) can see the actor
                # identity.
                _task_ctx.ctx = TaskContext(TaskID.from_random(), spec.actor_id)
                try:
                    state.instance = spec.cls(*args, **kwargs)
                finally:
                    _task_ctx.ctx = None
        except BaseException as e:  # noqa: BLE001
            release()
            state.death_cause = TaskError(e, task_repr=f"{spec.cls.__name__}.__init__")
            state.state = _ActorState.DEAD
            state.ready_event.set()
            self._drain_mailbox(state)
            return
        state.state = _ActorState.ALIVE
        state.ready_event.set()
        if first or not state.threads:
            self._start_actor_executors(state)

    def _start_remote_actor(self, state: _ActorState, node_id: NodeID) -> None:
        """Ship actor creation to a worker node; readiness arrives as an
        actor_ready/actor_dead frame (ref: gcs_actor_scheduler.h — the GCS
        leases a remote worker for creation the same way)."""
        node = self._remote_node(node_id)
        spec = state.spec
        if node is None or not node.alive:
            # Vanished between lease and dispatch: retry the FSM.
            if state.release is not None:
                state.release()
                state.release = None
            self._kill_actor_state(state, ActorDiedError(
                f"node {node_id} vanished before actor creation"),
                no_restart=False)
            return
        state.remote_node = node_id
        try:
            node.conn.send(("actor_create", serialization.dumps_inband(spec)))
        except (OSError, ConnectionError):
            state.remote_node = None
            if state.release is not None:
                state.release()
                state.release = None
            self._kill_actor_state(state, ActorDiedError(
                f"node {node_id} unreachable for actor creation"),
                no_restart=False)
            return
        except BaseException as e:  # noqa: BLE001 — unpicklable class/args
            state.remote_node = None
            if state.release is not None:
                state.release()
                state.release = None
            state.death_cause = TaskError(e, task_repr=f"{spec.cls.__name__}.__init__")
            state.state = _ActorState.DEAD
            state.ready_event.set()
            self._drain_mailbox(state)
        # state stays PENDING (or RESTARTING) until the node answers; the
        # executor loops wait on ready_event before touching the mailbox.

    def _forward_actor_task(self, state: _ActorState, spec: TaskSpec) -> None:
        """Mailbox consumer path for remotely-hosted actors: ship the call;
        its completion frame lands the results."""
        node = self._remote_node(state.remote_node) \
            if state.remote_node is not None else None
        if node is None or not node.alive:
            self._fail_task(spec, ActorDiedError(
                f"actor node {state.remote_node} died"), retry=False)
            return
        self._emit_event(spec.task_id, spec.name, "SUBMITTED_TO_WORKER",
                         node_id=str(node.node_id))
        with self._remote_lock:
            self._remote_inflight[spec.task_id] = (spec, _noop, node.node_id)
        try:
            node.conn.send(("actor_task", str(spec.actor_id),
                            serialization.dumps_inband(spec)))
        except (OSError, ConnectionError):
            with self._remote_lock:
                self._remote_inflight.pop(spec.task_id, None)
            self._declare_node_lost(node)
            self._fail_task(spec, ActorDiedError(
                f"actor node {node.node_id} unreachable"), retry=False)
        except BaseException as e:  # noqa: BLE001
            with self._remote_lock:
                self._remote_inflight.pop(spec.task_id, None)
            self._fail_task(spec, e, retry=False)

    def _resolve_values(self, args, kwargs):
        return (tuple(self._resolve_ref(a) for a in args),
                {k: self._resolve_ref(v) for k, v in kwargs.items()})

    def _start_actor_executors(self, state: _ActorState) -> None:
        if state.remote_node is not None:
            # Remote host: ONE ordered forwarding thread (concurrency is
            # enforced by the hosting node's own executors).
            t = threading.Thread(target=self._actor_sync_loop, args=(state,), daemon=True)
            t.start()
            state.threads = [t]
            return
        if state.is_async:
            t = threading.Thread(target=self._actor_async_loop, args=(state,), daemon=True)
            t.start()
            state.threads = [t]
        else:
            n = max(1, state.spec.max_concurrency)
            state.threads = []
            for _ in range(n):
                t = threading.Thread(target=self._actor_sync_loop, args=(state,), daemon=True)
                t.start()
                state.threads.append(t)

    def _actor_sync_loop(self, state: _ActorState) -> None:
        while True:
            item = state.mailbox.get()
            if item is None:
                return
            spec: TaskSpec = item
            if state.state in (_ActorState.RESTARTING, _ActorState.PENDING):
                # Wait out a restart / a remote creation still in flight
                # instead of calling into a torn-down or not-yet-built
                # instance (ready_event is set on ALIVE or DEAD).
                state.ready_event.wait(timeout=300)
            if state.state != _ActorState.ALIVE:
                self._fail_task(spec, ActorDiedError(cause=state.death_cause), retry=False)
                continue
            if state.remote_node is not None:
                self._forward_actor_task(state, spec)
            else:
                self._execute_actor_task(state, spec)

    def _actor_async_loop(self, state: _ActorState) -> None:
        loop = asyncio.new_event_loop()
        state.loop = loop
        sem = asyncio.Semaphore(max(1, state.spec.max_concurrency))

        async def run_one(spec: TaskSpec):
            try:
                async with sem:
                    await self._execute_actor_task_async(state, spec)
            except asyncio.CancelledError:
                # Cancelled while still queued on the concurrency
                # semaphore — the executor never saw this call, so its
                # refs must be resolved here or the caller hangs.
                self._fail_task(spec, ActorDiedError(cause=state.death_cause),
                                retry=False)
                raise

        async def pump():
            while True:
                item = await loop.run_in_executor(None, state.mailbox.get)
                if item is None:
                    return
                if state.state in (_ActorState.RESTARTING, _ActorState.PENDING):
                    await loop.run_in_executor(
                        None, state.ready_event.wait, 300)
                if state.state != _ActorState.ALIVE:
                    self._fail_task(item, ActorDiedError(cause=state.death_cause), retry=False)
                    continue
                if state.remote_node is not None:
                    # Restart landed on a worker node: forward instead of
                    # executing against the (gone) local instance.
                    self._forward_actor_task(state, item)
                    continue
                # detached_ok: reaped by the all_tasks cancel sweep after pump()
                loop.create_task(run_one(item))

        try:
            loop.run_until_complete(pump())
            # The actor is dead (pump only returns on the death sentinel):
            # calls still executing on this loop would otherwise be
            # abandoned with their refs forever unresolved — every caller
            # blocked in get()/get_async() on them would hang.  Cancel
            # them and run the cancellations to completion so each call
            # fails over to ActorDiedError (see _execute_actor_task_async).
            pending = [t for t in asyncio.all_tasks(loop) if not t.done()]
            for task in pending:
                task.cancel()
            if pending:
                loop.run_until_complete(
                    asyncio.gather(*pending, return_exceptions=True))
        finally:
            loop.close()

    def _execute_actor_task(self, state: _ActorState, spec: TaskSpec) -> None:
        ctx = TaskContext(spec.task_id, spec.actor_id)
        self._running[spec.task_id] = ctx
        _task_ctx.ctx = ctx
        self._emit_event(spec.task_id, spec.name, "RUNNING")
        worker = state.proc_worker
        try:
            with tracing.task_execute_span(spec):
                args, kwargs = self._resolve_args(spec)
                if spec.method_name == EXEC_FN_METHOD and spec.func is not None:
                    # Shipped-function actor task (compiled-DAG resident
                    # loops): run spec.func against the instance — the
                    # instance has no such method to look up.
                    if worker is not None:
                        result = worker.actor_exec(
                            serialization.dumps(spec.func), args, kwargs)
                    else:
                        result = spec.func(state.instance, *args, **kwargs)
                elif worker is not None:
                    if spec.generator:
                        # Stream the method's items over the multiplexed
                        # worker pipe into the generator machinery.
                        self._run_generator(
                            spec, args, kwargs,
                            iterator=worker.actor_call_gen(
                                spec.method_name, args, kwargs))
                        result = None
                    else:
                        result = worker.actor_call(
                            spec.method_name, args, kwargs)
                elif spec.generator:
                    method = getattr(state.instance, spec.method_name)
                    saved, spec.func = spec.func, method
                    try:
                        self._run_generator(spec, args, kwargs)
                    finally:
                        spec.func = saved
                    result = None
                else:
                    method = getattr(state.instance, spec.method_name)
                    result = method(*args, **kwargs)
            if not spec.generator:
                self._store_results(spec, result)
            self._emit_event(spec.task_id, spec.name, "FINISHED")
        except _ActorExit as e:
            self._store_results(spec, None)
            self._kill_actor_state(state, ActorDiedError("exit_actor() was called"), no_restart=True)
        except WorkerCrashedError as e:
            # The actor's host process died: fail this call and run the
            # restart FSM (ref: gcs_actor_manager.h actor restart on worker
            # death; max_restarts honored by _kill_actor_state).  Only the
            # thread whose crash matches the CURRENT worker triggers the
            # restart — with max_concurrency > 1, later threads observing the
            # same crash must not discard the freshly restarted worker and
            # burn an extra restart.
            self._fail_task(spec, ActorDiedError(cause=e), retry=False)
            if state.proc_worker is worker:
                self._kill_actor_state(
                    state, ActorDiedError(f"actor worker process died: {e}"),
                    no_restart=False)
        except BaseException as e:  # noqa: BLE001
            self._fail_task(spec, TaskError(e, task_repr=spec.name), retry=False)
        finally:
            _task_ctx.ctx = None
            self._running.pop(spec.task_id, None)

    async def _execute_actor_task_async(self, state: _ActorState, spec: TaskSpec) -> None:
        self._emit_event(spec.task_id, spec.name, "RUNNING")
        try:
            with tracing.task_execute_span(spec):
                args, kwargs = self._resolve_args(spec)
                method = getattr(state.instance, spec.method_name)
                result = method(*args, **kwargs)
                if inspect.isawaitable(result):
                    result = await result
            self._store_results(spec, result)
            self._emit_event(spec.task_id, spec.name, "FINISHED")
        except _ActorExit:
            self._store_results(spec, None)
            self._kill_actor_state(state, ActorDiedError("exit_actor() was called"), no_restart=True)
        except asyncio.CancelledError:
            # The actor died with this call in flight (kill/preemption
            # cancels the loop's tasks on the way down): resolve the refs
            # with the death cause — callers classify ActorDiedError as
            # retryable, a bare TaskError they would surface to the user.
            self._fail_task(spec, ActorDiedError(cause=state.death_cause),
                            retry=False)
        except BaseException as e:  # noqa: BLE001
            self._fail_task(spec, TaskError(e, task_repr=spec.name), retry=False)

    def submit_actor_task(self, actor_id: ActorID, spec: TaskSpec) -> Any:
        if tracing.is_tracing_enabled():
            with tracing.span(f"submit::{spec.name}",
                              attributes={"task_id": spec.task_id,
                                          "actor_id": actor_id}):
                tracing.inject_task_spec(spec)
                return self._submit_actor_task_inner(actor_id, spec)
        return self._submit_actor_task_inner(actor_id, spec)

    def _submit_actor_task_inner(self, actor_id: ActorID, spec: TaskSpec) -> Any:
        state = self._actors.get(actor_id)
        if state is None:
            raise ActorDiedError(f"Unknown actor {actor_id}")
        if state.state == _ActorState.DEAD:
            ref = ObjectRef(ObjectID.for_task_return(spec.task_id, 0), owner=self.worker_id)
            self._fail_task(spec, ActorDiedError(cause=state.death_cause), retry=False)
            return ref
        refs = [
            ObjectRef(ObjectID.for_task_return(spec.task_id, i), owner=self.worker_id)
            for i in range(spec.num_returns)
        ]
        gen = None
        if spec.generator:
            gen = ObjectRefGenerator(spec.task_id)
            self._generators[spec.task_id] = gen
        self._emit_event(spec.task_id, spec.name, "PENDING_ACTOR_TASK")
        self._inflight.add(spec.task_id)
        state.mailbox.put(spec)
        if spec.generator:
            return gen
        return refs[0] if spec.num_returns == 1 else refs

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True) -> None:
        state = self._actors.get(actor_id)
        if state is None:
            return
        self._kill_actor_state(state, ActorDiedError("ray_tpu.kill() was called"), no_restart)

    def _kill_actor_state(self, state: _ActorState, cause: ActorDiedError, no_restart: bool) -> None:
        died_terminally = False
        with state.lock:
            spec = state.spec
            can_restart = (not no_restart) and (
                spec.max_restarts == -1 or state.num_restarts < spec.max_restarts
            )
            if state.release is not None:
                state.release()
                state.release = None
            state.instance = None
            if state.proc_worker is not None:
                self.process_pool.discard(state.proc_worker)
                state.proc_worker = None
            if state.remote_node is not None:
                # Tell the hosting node to tear down its instance (it must
                # not run its own restart FSM after an explicit head kill);
                # on node death remote_node was already cleared.
                node = self._remote_node(state.remote_node)
                state.remote_node = None
                if node is not None and node.alive:
                    try:
                        node.conn.send(("kill_actor", str(spec.actor_id), True))
                    except (OSError, ConnectionError):
                        pass
            if can_restart:
                state.state = _ActorState.RESTARTING
                state.num_restarts += 1
                state.ready_event.clear()
                try:
                    self._exec_pool.submit(self._start_actor, state, first=False)
                except RuntimeError:
                    state.death_cause = ActorDiedError("runtime is shutting down")
                    state.state = _ActorState.DEAD
                    state.ready_event.set()
            else:
                state.state = _ActorState.DEAD
                state.death_cause = cause
                with self._actors_lock:
                    if spec.name and self._named_actors.get((spec.namespace, spec.name)) == spec.actor_id:
                        del self._named_actors[(spec.namespace, spec.name)]
                for _ in state.threads:
                    state.mailbox.put(None)
                died_terminally = True
        if died_terminally and not self._dispatcher_stop.is_set():
            # Actor-death sentinel: snapshot the black box while the spans
            # that explain the death are still in the ring (best-effort,
            # flood-controlled; skipped during runtime shutdown where mass
            # actor teardown is expected, not a failure).
            from ray_tpu.util import flight_recorder

            flight_recorder.trigger_dump("actor_death", {
                "actor_id": str(spec.actor_id),
                "name": spec.name or "",
                "class": getattr(spec, "class_name", "") or "",
                "cause": str(cause),
                # Node attribution: the cluster autoscaler's health gate
                # keys postmortems on the node that produced them.
                "node": str(state.node_id) if state.node_id else "",
            })

    def _drain_mailbox(self, state: _ActorState) -> None:
        while True:
            try:
                spec = state.mailbox.get_nowait()
            except queue.Empty:
                return
            if spec is not None:
                self._fail_task(spec, ActorDiedError(cause=state.death_cause), retry=False)

    def get_actor_state(self, actor_id: ActorID) -> Optional[_ActorState]:
        return self._actors.get(actor_id)

    def get_named_actor(self, name: str, namespace: Optional[str] = None) -> ActorID:
        key = (namespace or self.namespace, name)
        with self._actors_lock:
            actor_id = self._named_actors.get(key)
        if actor_id is None:
            raise ValueError(f"Failed to look up actor '{name}' in namespace '{key[0]}'")
        return actor_id

    def list_actor_states(self) -> List[dict]:
        with self._actors_lock:
            return [
                {
                    "actor_id": str(aid),
                    "class_name": st.spec.cls.__name__,
                    "state": st.state,
                    "name": st.spec.name or "",
                    "num_restarts": st.num_restarts,
                    "node_id": str(st.node_id) if st.node_id else "",
                }
                for aid, st in self._actors.items()
            ]

    # --------------------------------------------------------------- shutdown
    def shutdown(self) -> None:
        self._dispatcher_stop.set()
        self._ready.put(None)
        if self.node_server is not None:
            for node in self._remote_nodes_snapshot():
                node.alive = False  # suppress node-lost recovery on EOF
                try:
                    node.conn.send(("shutdown",))
                except (OSError, ConnectionError):
                    pass
            self.node_server.stop()
            self.node_server = None
        with self._actors_lock:
            actors = list(self._actors.values())
        for state in actors:
            state.state = _ActorState.DEAD
            if state.proc_worker is not None:
                state.proc_worker.kill()
                state.proc_worker = None
            for _ in state.threads or [None]:
                state.mailbox.put(None)
        if self._memory_monitor is not None:
            self._memory_monitor.stop()
            self._memory_monitor = None
        self.process_pool.shutdown()
        from ray_tpu._private.process_pool import stop_log_monitor

        stop_log_monitor()
        self._exec_pool.shutdown(wait=False, cancel_futures=True)
        from ray_tpu._private import borrowing

        borrowing.release_all()  # return outstanding borrows to their owners
        if self.object_server is not None:
            self.object_server.stop()
            self.object_server = None
        self.store.shutdown()
        self.refcounter.clear()


class _LeasedWorker:
    """Kill-candidate record for the memory monitor."""

    __slots__ = ("worker", "retriable", "started_at")

    def __init__(self, worker, retriable: bool):
        self.worker = worker
        self.retriable = retriable
        self.started_at = time.monotonic()


class _ActorExit(BaseException):
    """Raised by exit_actor() to terminate the current actor."""


def get_runtime() -> Runtime:
    if _runtime is None:
        raise RuntimeError("ray_tpu.init() has not been called")
    return _runtime


def runtime_or_none() -> Optional[Runtime]:
    return _runtime


def install_runtime(rt) -> None:
    """Install a runtime implementation (process workers install their
    ClientRuntime proxy here so the full API works in the child)."""
    global _runtime
    with _runtime_lock:
        _runtime = rt


def init_runtime(**kwargs) -> Runtime:
    global _runtime
    with _runtime_lock:
        if _runtime is None:
            _runtime = Runtime(**kwargs)
        return _runtime


def shutdown_runtime() -> None:
    global _runtime
    with _runtime_lock:
        if _runtime is not None:
            _runtime.shutdown()
            _runtime = None
