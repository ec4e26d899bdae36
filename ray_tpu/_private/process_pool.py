"""Process worker pool for GIL-isolated task execution.

TPU-native analogue of the reference's WorkerPool + worker lease protocol
(ref: src/ray/raylet/worker_pool.h:216, normal_task_submitter.h:74).  In the
reference every task runs in a leased worker *process*; here processes are the
*opt-in* tier (``options(isolation="process")`` or CPU-heavy library paths),
because on TPU hosts the chips are owned by one JAX client in the driver
process and compute-bound work releases the GIL inside XLA anyway.

Protocol per worker (spawn ctx; a fork after JAX/TPU init is unsafe):
  driver -> worker: ("exec"|"exec_gen", seq, fn_id, fn_bytes|None, args_spec)
                    ("actor_call"|"actor_call_gen", seq, method, args_spec)
  worker -> driver: ("ok", seq, result_spec) | ("err", seq, flat_exc)
                    | ("yield", seq, item_spec)   [streaming kinds]
where a spec is ("inline", bytes) or ("plasma", key) — payloads above
``plasma_handoff_threshold`` travel through the native shared-memory arena
(ray_tpu/native/src/plasma.cc) zero-copy instead of the pipe, the analogue of
the reference passing ObjectIDs + plasma fds rather than bytes
(ref: plasma/client.h, fling.cc).

The pipe is MULTIPLEXED by seq: the driver side has one reader thread per
worker routing replies to per-request queues, and the worker side runs
exec/actor_call requests on threads (bounded) with a send lock — so a
process actor with max_concurrency > 1 really executes concurrently, and
streaming generators interleave with other requests (ref: core_worker's
concurrent actor calls + streaming generator protocol, _raylet.pyx:1097).
Functions are cached worker-side by fn_id so hot loops ship only args
(ref: function table export via GCS KV, _private/function_manager.py).
Leases are reused: a released worker goes back to the idle pool keyed by
runtime-env hash.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import serialization
from ray_tpu._private.config import GLOBAL_CONFIG


def _attach_arena(path: Optional[str]):
    if not path:
        return None
    try:
        from ray_tpu.native.plasma import PlasmaClient

        return PlasmaClient(path, create=False)
    except Exception:
        return None


def _spec_put(arena, key_hint: str, payload: bytes):
    """Choose the transport for one payload."""
    if arena is not None and len(payload) > GLOBAL_CONFIG.plasma_handoff_threshold:
        try:
            arena.put_bytes(key_hint, payload)
            return ("plasma", key_hint)
        except (MemoryError, ValueError):
            pass  # arena full or key collision: the pipe always works
    return ("inline", payload)


def _spec_take(arena, spec) -> bytes:
    """Fetch and consume one payload (plasma objects are freed here)."""
    kind, val = spec
    if kind == "inline":
        return val
    if arena is None:
        raise RuntimeError(
            f"peer sent plasma handoff {val} but this side has no arena client")
    data = arena.get_bytes(val, timeout=30)
    if data is None:
        raise RuntimeError(f"plasma handoff object {val} missing")
    arena.release(val)  # creator's ref
    arena.delete(val)
    return data


def _spec_cleanup(arena, spec) -> None:
    """Best-effort free of an unconsumed plasma handoff (idempotent: no-op if
    the peer already consumed it via _spec_take)."""
    if arena is None or spec[0] != "plasma":
        return
    try:
        arena.release(spec[1])
        arena.delete(spec[1])
    except Exception:
        pass


def _actor_task_context(actor_id):
    """Worker-side actor-scoped context manager so exit_actor() and
    get_runtime_context() work inside process-isolated actor methods."""
    from contextlib import contextmanager

    @contextmanager
    def cm():
        from ray_tpu._private.ids import TaskID
        from ray_tpu._private.runtime import TaskContext, _task_ctx

        _task_ctx.ctx = TaskContext(TaskID.from_random(), actor_id)
        try:
            yield
        finally:
            _task_ctx.ctx = None

    return cm()


def _worker_main(conn, arena_path: Optional[str], back_conn=None) -> None:
    # Keep workers off the TPU: the driver process owns the chips, and a
    # second process that asks libtpu for them dies at its first jax call.
    # Assigned, not defaulted: a TPU machine exports JAX_PLATFORMS=tpu,cpu
    # and the child inherits it.  A task that really owns chips says so in
    # its runtime_env env_vars, which are applied after this.
    os.environ["JAX_PLATFORMS"] = "cpu"
    # On-demand stack dumps (`ray_tpu stack`, ref: py-spy via the reporter
    # agent): SIGUSR1 → faulthandler dump readable by the driver.
    from ray_tpu._private.stack_profiler import install_worker_dump_handler

    install_worker_dump_handler()
    # Worker stdout/stderr → per-pid session log files, tailed back to the
    # driver by the LogMonitor (ref: _private/log_monitor.py:103).
    from ray_tpu._private.log_monitor import redirect_worker_output

    redirect_worker_output()
    fn_cache: Dict[str, Any] = {}
    actor_instance: List[Any] = [None]  # box: set by actor_new
    arena = _attach_arena(arena_path)
    if back_conn is not None:
        # Nested-API support: install the proxy runtime so user code in this
        # worker can call ray_tpu.remote/get/put/wait (client_runtime.py).
        from ray_tpu._private.client_runtime import ClientRuntime
        from ray_tpu._private.runtime import install_runtime

        install_runtime(ClientRuntime(
            back_conn, worker_id=f"proc-worker-{os.getpid()}"))

    send_lock = threading.Lock()
    #: Streams the driver abandoned (cancel/early error): the worker's
    #: yield loops check membership and stop pumping the user generator.
    stopped_streams: set = set()

    def send(msg) -> None:
        with send_lock:
            conn.send_bytes(serialization.dumps(msg))

    def reply_ok(seq, payload):
        send(("ok", seq, payload))

    def reply_err(seq, e):
        import traceback

        tb = traceback.format_exc()
        try:
            blob = serialization.dumps((e, tb))
        except Exception:
            blob = serialization.dumps((RuntimeError(repr(e)), tb))
        send(("err", seq, blob))

    def run_exec(seq, fn_id, fn_bytes, args_spec, streaming):
        try:
            if fn_id not in fn_cache:
                if fn_bytes is not None:
                    fn_cache[fn_id] = serialization.loads(fn_bytes)
                else:
                    # Concurrent first-use race: another in-flight request
                    # carries the bytes; wait for its thread to cache them.
                    deadline = time.monotonic() + 10
                    while fn_id not in fn_cache:
                        if time.monotonic() > deadline:
                            raise RuntimeError(
                                f"function {fn_id} never arrived")
                        time.sleep(0.005)
            fn = fn_cache[fn_id]
            flat_args = _spec_take(arena, args_spec)
            args, kwargs = serialization.deserialize_flat(memoryview(flat_args))
            if streaming:
                n = 0
                for item in fn(*args, **kwargs):
                    if seq in stopped_streams:
                        break  # driver abandoned the stream
                    payload = serialization.serialize(item).to_bytes()
                    send(("yield", seq, _spec_put(
                        arena, f"res:{os.getpid()}:{seq}:{n}", payload)))
                    n += 1
                stopped_streams.discard(seq)
                reply_ok(seq, None)
                return
            result = fn(*args, **kwargs)
            payload = serialization.serialize(result).to_bytes()
            reply_ok(seq, _spec_put(arena, f"res:{os.getpid()}:{seq}", payload))
        except BaseException as e:  # noqa: BLE001 — errors cross the boundary
            reply_err(seq, e)

    def run_actor_call(seq, method_name, args_spec, streaming):
        try:
            if actor_instance[0] is None:
                raise RuntimeError("actor_call before actor_new")
            method = getattr(actor_instance[0], method_name)
            flat_args = _spec_take(arena, args_spec)
            args, kwargs = serialization.deserialize_flat(memoryview(flat_args))
            # Run under an actor-scoped task context so exit_actor() and
            # get_runtime_context() work inside the method; _ActorExit
            # crosses back via reply_err and is unwrapped driver-side.
            with _actor_task_context(actor_instance[1]):
                if streaming:
                    n = 0
                    for item in method(*args, **kwargs):
                        if seq in stopped_streams:
                            break  # driver abandoned the stream
                        payload = serialization.serialize(item).to_bytes()
                        send(("yield", seq, _spec_put(
                            arena, f"res:{os.getpid()}:{seq}:{n}", payload)))
                        n += 1
                    stopped_streams.discard(seq)
                    reply_ok(seq, None)
                    return
                result = method(*args, **kwargs)
            payload = serialization.serialize(result).to_bytes()
            reply_ok(seq, _spec_put(arena, f"res:{os.getpid()}:{seq}", payload))
        except BaseException as e:  # noqa: BLE001
            reply_err(seq, e)

    #: Bound on concurrent in-worker requests (actor max_concurrency is
    #: enforced by the driver's mailbox threads; this is a backstop).
    work_sem = threading.BoundedSemaphore(64)

    def spawn(target, *args):
        def run():
            with work_sem:
                target(*args)

        threading.Thread(target=run, daemon=True).start()

    while True:
        try:
            msg = conn.recv_bytes()
        except (EOFError, OSError):
            return
        req = serialization.loads(msg)
        kind = req[0]
        if kind == "setup_env":
            # Applied once per worker; the pool keys leases by env hash so a
            # worker only ever hosts one runtime env (ref: worker_pool.h
            # runtime-env-keyed caching).
            try:
                from ray_tpu._private.runtime_env import apply_in_worker

                apply_in_worker(req[1])
                reply_ok(0, None)
            except BaseException as e:  # noqa: BLE001
                reply_err(0, e)
        elif kind in ("exec", "exec_gen"):
            _, seq, fn_id, fn_bytes, args_spec = req
            # Off-thread: concurrent requests (max_concurrency > 1 actors,
            # interleaved streams) must not serialize behind one another.
            spawn(run_exec, seq, fn_id, fn_bytes, args_spec,
                  kind == "exec_gen")
        elif kind == "actor_new":
            # This worker becomes a dedicated actor host: instantiate the
            # class and hold it for the worker's lifetime (ref: the reference
            # runs every actor in its own worker process by default).
            _, seq, cls_bytes, actor_id, args_spec = req
            try:
                cls = serialization.loads(cls_bytes)
                flat_args = _spec_take(arena, args_spec)
                args, kwargs = serialization.deserialize_flat(memoryview(flat_args))
                with _actor_task_context(actor_id):
                    actor_instance[0] = cls(*args, **kwargs)
                actor_instance.append(actor_id)
                reply_ok(seq, None)
            except BaseException as e:  # noqa: BLE001
                reply_err(seq, e)
        elif kind in ("actor_call", "actor_call_gen"):
            _, seq, method_name, args_spec = req
            spawn(run_actor_call, seq, method_name, args_spec,
                  kind == "actor_call_gen")
        elif kind == "actor_exec":
            # Run an arbitrary shipped function against the resident actor
            # instance (compiled-DAG executor loops live here: long-running,
            # multiplexed beside ordinary calls).
            _, seq, fn_bytes, args_spec = req

            def run_actor_exec(seq=seq, fn_bytes=fn_bytes,
                               args_spec=args_spec):
                try:
                    if actor_instance[0] is None:
                        raise RuntimeError("actor_exec before actor_new")
                    if arena is not None:
                        # Unpickled shm channels attach by path: reuse THIS
                        # worker's client instead of opening a second mmap.
                        try:
                            from ray_tpu.dag.channel import seed_arena_client

                            seed_arena_client(arena.path, arena)
                        except Exception:
                            pass
                    fn = serialization.loads(fn_bytes)
                    flat = _spec_take(arena, args_spec)
                    args, kwargs = serialization.deserialize_flat(
                        memoryview(flat))
                    with _actor_task_context(
                            actor_instance[1] if len(actor_instance) > 1
                            else None):
                        result = fn(actor_instance[0], *args, **kwargs)
                    payload = serialization.serialize(result).to_bytes()
                    reply_ok(seq, _spec_put(
                        arena, f"res:{os.getpid()}:{seq}", payload))
                except BaseException as e:  # noqa: BLE001
                    reply_err(seq, e)

            spawn(run_actor_exec)
        elif kind == "gen_stop":
            stopped_streams.add(req[1])
        elif kind == "shutdown":
            return


_HANDOFF_COUNTER = 0
_HANDOFF_LOCK = threading.Lock()


def _next_handoff_key(prefix: str) -> str:
    global _HANDOFF_COUNTER
    with _HANDOFF_LOCK:
        _HANDOFF_COUNTER += 1
        return f"{prefix}:{os.getpid()}:{_HANDOFF_COUNTER}"


_LOG_MONITOR = None
_LOG_MONITOR_LOCK = threading.Lock()


def _ensure_log_monitor() -> None:
    """One driver-wide tailer streaming worker logs back to this terminal
    while config.log_to_driver is on (ref: LogMonitor publishes to the
    driver via GCS pubsub; in-process here)."""
    global _LOG_MONITOR
    if not GLOBAL_CONFIG.log_to_driver:
        return
    with _LOG_MONITOR_LOCK:
        if _LOG_MONITOR is None:
            from ray_tpu._private.log_monitor import LogMonitor

            _LOG_MONITOR = LogMonitor().start()


def stop_log_monitor() -> None:
    """Runtime shutdown: end the tailer so a later init (possibly with
    log_to_driver=False) doesn't inherit a still-streaming thread."""
    global _LOG_MONITOR
    with _LOG_MONITOR_LOCK:
        if _LOG_MONITOR is not None:
            _LOG_MONITOR.stop()
            _LOG_MONITOR = None


class _ProcWorker:
    def __init__(self, arena_path: Optional[str] = None, arena=None,
                 env_key: str = "", env_payload: Optional[dict] = None) -> None:
        import sys

        self.env_key = env_key

        # Export resolved dirs so the spawned child (which sees only config
        # DEFAULTS) writes its SIGUSR1 dump file and stdout/stderr logs
        # where this driver will look for them.
        from ray_tpu._private.log_monitor import log_dir
        from ray_tpu._private.stack_profiler import dump_dir

        dump_dir(export=True)
        log_dir(export=True)
        _ensure_log_monitor()

        ctx = mp.get_context("spawn")
        self.conn, child_conn = ctx.Pipe()
        # Second pipe: the worker-initiated nested-API backchannel, serviced
        # by a dedicated driver thread (client_runtime.serve_backchannel) so
        # a child blocking in get() is independent of this request pipe.
        back_parent, back_child = ctx.Pipe()
        self.proc = ctx.Process(
            target=_worker_main, args=(child_conn, arena_path, back_child),
            daemon=True)
        # Drivers run from a pipe/heredoc have __main__.__file__ == "<stdin>";
        # spawn's prepare step would try to re-execute that path in the child
        # and crash it.  Mask the pseudo-file for the duration of start().
        main_mod = sys.modules.get("__main__")
        main_file = getattr(main_mod, "__file__", None)
        masked = main_file is not None and str(main_file).startswith("<")
        if masked:
            del main_mod.__file__
        try:
            self.proc.start()
        finally:
            if masked:
                main_mod.__file__ = main_file
        child_conn.close()
        back_child.close()
        from ray_tpu._private.client_runtime import serve_backchannel

        self._back_thread = threading.Thread(
            target=serve_backchannel, args=(back_parent,),
            name=f"backchannel-{self.proc.pid}", daemon=True)
        self._back_thread.start()
        self._arena = arena  # the pool's shared driver-side client
        import itertools
        import queue as queue_mod

        self._seq_counter = itertools.count(1)  # GIL-atomic next()
        self.sent_fns: set = set()
        self.last_used = time.monotonic()
        # The pipe is seq-multiplexed: sends serialize under this lock; a
        # reader thread routes replies (ok/err/yield) to per-seq queues, so
        # max_concurrency > 1 actors and interleaved streams really overlap.
        self._send_lock = threading.Lock()
        self._pending: Dict[int, "queue_mod.SimpleQueue"] = {}
        self._pending_lock = threading.Lock()
        self._dead = False
        self._queue_mod = queue_mod
        self._reader = threading.Thread(
            target=self._read_loop, name=f"procworker-read-{self.proc.pid}",
            daemon=True)
        self._reader.start()
        if env_payload is not None:
            from ray_tpu.exceptions import TaskError

            q = self._register(0)
            with self._send_lock:
                self.conn.send_bytes(
                    serialization.dumps(("setup_env", env_payload)))
            kind, payload = q.get()
            self._unregister(0)
            if kind == "err":
                exc, tb = serialization.loads(payload)
                self.kill()
                raise TaskError(exc, tb=tb)
            if kind == "crash":
                self.kill()
                raise RuntimeError("process worker died during env setup")

    # ----------------------------------------------------------- multiplexer
    def _register(self, seq: int):
        q = self._queue_mod.SimpleQueue()
        with self._pending_lock:
            if self._dead:
                q.put(("crash", None))
            self._pending[seq] = q
        return q

    def _unregister(self, seq: int) -> None:
        with self._pending_lock:
            self._pending.pop(seq, None)

    def _read_loop(self) -> None:
        while True:
            try:
                reply = serialization.loads(self.conn.recv_bytes())
            except (EOFError, OSError):
                break
            except Exception:
                break
            rkind, seq, payload = reply
            with self._pending_lock:
                q = self._pending.get(seq)
            if q is not None:
                q.put((rkind, payload))
            elif rkind == "yield":
                # Stream abandoned before this item arrived: a plasma
                # payload would otherwise pin arena memory forever.
                _spec_cleanup(self._arena, payload)
        # Worker gone: wake every in-flight request with a crash marker.
        with self._pending_lock:
            self._dead = True
            waiters = list(self._pending.values())
        for q in waiters:
            q.put(("crash", None))

    def _submit(self, kind: str, header_rest: tuple, args: tuple,
                kwargs: dict):
        """Ship one request; returns (seq, queue, args_spec)."""
        arena = self._arena
        seq = next(self._seq_counter)  # GIL-atomic
        flat_args = serialization.serialize((args, kwargs)).to_bytes()
        args_spec = _spec_put(arena, _next_handoff_key("args"), flat_args)
        header = (kind, seq) + header_rest
        q = self._register(seq)
        try:
            with self._send_lock:
                self.conn.send_bytes(serialization.dumps(header + (args_spec,)))
        except (EOFError, OSError) as e:
            from ray_tpu.exceptions import WorkerCrashedError

            self._unregister(seq)
            _spec_cleanup(arena, args_spec)
            raise WorkerCrashedError(f"process worker died: {e}") from e
        return seq, q, args_spec

    def _raise_reply_error(self, payload):
        from ray_tpu.exceptions import TaskError
        from ray_tpu._private.runtime import _ActorExit

        exc, tb = serialization.loads(payload)
        if isinstance(exc, _ActorExit):
            # exit_actor() inside a process actor: re-raise unwrapped so the
            # runtime's actor FSM sees it (runtime.py _execute_actor_task).
            raise exc
        raise TaskError(exc, tb=tb)

    def _roundtrip(self, kind: str, header_rest: tuple, args: tuple,
                   kwargs: dict, has_result: bool = True) -> Any:
        """One request/reply over the multiplexed pipe.

        Raises WorkerCrashedError if the process dies, TaskError on a
        worker-side exception."""
        from ray_tpu.exceptions import WorkerCrashedError

        arena = self._arena
        seq, q, args_spec = self._submit(kind, header_rest, args, kwargs)
        try:
            rkind, payload = q.get()
        finally:
            self._unregister(seq)
        self.last_used = time.monotonic()
        if rkind == "crash":
            # Reclaim the args if unconsumed, and the result object if the
            # worker got far enough to produce one before dying — a sealed-
            # but-unreported result would otherwise pin arena memory forever.
            _spec_cleanup(arena, args_spec)
            _spec_cleanup(arena, ("plasma", f"res:{self.proc.pid}:{seq}"))
            raise WorkerCrashedError("process worker died")
        if rkind == "ok":
            # The worker reached the result, so it consumed the args spec.
            if not has_result or payload is None:
                return None
            return serialization.deserialize_flat(
                memoryview(_spec_take(arena, payload)))
        # Error may have struck before the worker consumed the args.
        _spec_cleanup(arena, args_spec)
        self._raise_reply_error(payload)

    def _stream(self, kind: str, header_rest: tuple, args: tuple,
                kwargs: dict):
        """Streaming request: yields items as the worker produces them;
        terminates on the worker's ok (end) / err (raised) / crash."""
        from ray_tpu.exceptions import WorkerCrashedError

        arena = self._arena
        seq, q, args_spec = self._submit(kind, header_rest, args, kwargs)
        finished = False
        try:
            while True:
                rkind, payload = q.get()
                self.last_used = time.monotonic()
                if rkind == "yield":
                    yield serialization.deserialize_flat(
                        memoryview(_spec_take(arena, payload)))
                    continue
                if rkind == "ok":
                    finished = True
                    return
                finished = True
                if rkind == "crash":
                    _spec_cleanup(arena, args_spec)
                    raise WorkerCrashedError("process worker died mid-stream")
                _spec_cleanup(arena, args_spec)
                self._raise_reply_error(payload)
        finally:
            self._unregister(seq)
            if not finished:
                # Consumer abandoned the stream (cancel / early close):
                # tell the worker to stop pumping; items already in our
                # queue are reclaimed here, late ones by the reader's
                # dropped-yield cleanup.
                try:
                    with self._send_lock:
                        self.conn.send_bytes(
                            serialization.dumps(("gen_stop", seq)))
                except (EOFError, OSError):
                    pass
                while not q.empty():
                    rkind, payload = q.get()
                    if rkind == "yield":
                        _spec_cleanup(arena, payload)

    def execute(self, fn_id: str, fn_bytes: bytes, args: tuple, kwargs: dict) -> Any:
        """Run one task; raises WorkerCrashedError if the process dies."""
        send_fn = fn_bytes if fn_id not in self.sent_fns else None
        self.sent_fns.add(fn_id)
        return self._roundtrip("exec", (fn_id, send_fn), args, kwargs)

    def execute_gen(self, fn_id: str, fn_bytes: bytes, args: tuple,
                    kwargs: dict):
        """Run one GENERATOR task; yields items as the worker sends them."""
        send_fn = fn_bytes if fn_id not in self.sent_fns else None
        self.sent_fns.add(fn_id)
        return self._stream("exec_gen", (fn_id, send_fn), args, kwargs)

    def actor_new(self, cls_bytes: bytes, actor_id: str, args: tuple,
                  kwargs: dict) -> None:
        """Instantiate an actor in this worker (dedicates the worker)."""
        self._roundtrip("actor_new", (cls_bytes, actor_id), args, kwargs,
                        has_result=False)

    def actor_call(self, method_name: str, args: tuple, kwargs: dict) -> Any:
        """Invoke a method on the worker-resident actor instance."""
        return self._roundtrip("actor_call", (method_name,), args, kwargs)

    def actor_call_gen(self, method_name: str, args: tuple, kwargs: dict):
        """Invoke a GENERATOR method; yields items as the worker sends them."""
        return self._stream("actor_call_gen", (method_name,), args, kwargs)

    def actor_exec(self, fn_bytes: bytes, args: tuple, kwargs: dict) -> Any:
        """Run fn(instance, *args, **kwargs) against the worker-resident
        actor instance (compiled-DAG resident loops)."""
        return self._roundtrip("actor_exec", (fn_bytes,), args, kwargs)

    def alive(self) -> bool:
        return self.proc.is_alive()

    def kill(self) -> None:
        try:
            self.proc.terminate()
        except Exception:
            pass


class ProcessPool:
    """Idle-pool of reusable spawned workers with an upper bound."""

    def __init__(self, arena_path: Optional[str] = None, arena=None) -> None:
        #: Idle workers keyed by runtime-env hash ("" = no env) — the
        #: reference's runtime-env-keyed WorkerPool cache (worker_pool.h:216).
        self._idle: Dict[str, List[_ProcWorker]] = {}
        self._lock = threading.Lock()
        self._count = 0
        self.arena_path = arena_path
        # One shared driver-side arena client for all workers (one mmap + fd
        # per process, as plasma.py documents) — normally the ObjectStore's
        # own client, passed in by the runtime.
        self._arena = arena if arena is not None else _attach_arena(arena_path)

    def lease(self, env_key: str = "",
              env_payload: Optional[dict] = None) -> _ProcWorker:
        with self._lock:
            pool = self._idle.get(env_key, [])
            while pool:
                w = pool.pop()
                if w.alive():
                    return w
                self._count -= 1
            self._count += 1
        try:
            return _ProcWorker(self.arena_path, self._arena,
                               env_key=env_key, env_payload=env_payload)
        except BaseException:
            with self._lock:
                self._count -= 1
            raise

    def release(self, worker: _ProcWorker) -> None:
        if not worker.alive():
            with self._lock:
                self._count -= 1
            return
        with self._lock:
            if self._count <= GLOBAL_CONFIG.max_process_workers:
                self._idle.setdefault(worker.env_key, []).append(worker)
                return
            self._count -= 1
        worker.kill()

    def discard(self, worker: _ProcWorker) -> None:
        with self._lock:
            self._count -= 1
        worker.kill()

    def shutdown(self) -> None:
        with self._lock:
            pools, self._idle, self._count = self._idle, {}, 0
        workers = [w for pool in pools.values() for w in pool]
        for w in workers:
            try:
                w.conn.send_bytes(serialization.dumps(("shutdown",)))
            except Exception:
                pass
            w.kill()
