"""ray_tpu — a TPU-native distributed AI framework.

Capability surface of the reference (Ray 2.41.0) redesigned around JAX/XLA:
tasks, actors and an ownership-based object store in the core; collectives as
compiled XLA ops over ICI meshes; Train/Data/Tune/Serve/RL libraries on top.

Public core API mirrors the reference's (ref: python/ray/_private/worker.py —
init:1275, get:2668, put:2804, wait:2869; remote_function.py:41; actor.py:602)
so a Ray user can switch with minimal edits.
"""

from __future__ import annotations

import inspect
import time
from typing import Any, Dict, Optional, Sequence, Union

from ray_tpu import exceptions
from ray_tpu._private import runtime as _rt
from ray_tpu._private.ids import ActorID, JobID, NodeID, ObjectID, TaskID
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.runtime import ObjectRefGenerator
from ray_tpu.actor import ActorClass, ActorHandle, exit_actor
from ray_tpu.remote_function import RemoteFunction

__version__ = "0.1.0"

#: ``time.time()`` when the package had been imported: where the set-up's
#: account (``util.device_telemetry.setup_account``) starts if the kernel
#: does not say when the process did.
IMPORTED_AT = time.time()

__all__ = [
    "init", "shutdown", "is_initialized", "remote", "get", "put", "wait",
    "cancel", "kill", "get_actor", "method", "nodes", "cluster_resources",
    "available_resources", "timeline", "ObjectRef", "ObjectRefGenerator",
    "ActorHandle", "exceptions", "exit_actor", "get_runtime_context",
]


def init(
    address: Optional[str] = None,
    *,
    num_cpus: Optional[int] = None,
    num_tpus: Optional[int] = None,
    resources: Optional[Dict[str, float]] = None,
    labels: Optional[Dict[str, str]] = None,
    namespace: str = "default",
    ignore_reinit_error: bool = False,
    _system_config: Optional[dict] = None,
    **_compat_kwargs: Any,
):
    """Start the runtime (ref: worker.py:1275 ray.init).

    ``address="ray://host:port"`` connects this process as a REMOTE DRIVER
    to a cluster serving `ray_tpu.util.client.ClientServer` — the full
    task/actor/object API proxies over TCP (ref: util/client ray:// mode).
    Any other address (or None) starts the local runtime.
    """
    if _rt.runtime_or_none() is not None:
        if address and address.startswith("ray://"):
            # Returning the LOCAL runtime here would silently run "remote"
            # work locally — always loud.
            raise RuntimeError(
                f"ray_tpu.init(address={address!r}) requested a remote "
                "cluster but a runtime is already active in this process; "
                "call ray_tpu.shutdown() first")
        if ignore_reinit_error:
            return _rt.get_runtime()
        raise RuntimeError("ray_tpu.init() called twice; pass ignore_reinit_error=True")
    from ray_tpu.util import device_telemetry

    with device_telemetry.setup_span("runtime.init"):
        if address and address.startswith("ray://"):
            from ray_tpu.util.client import connect

            return connect(address)
        return _rt.init_runtime(
            num_cpus=num_cpus,
            num_tpus=num_tpus,
            resources=resources,
            labels=labels,
            namespace=namespace,
            _system_config=_system_config,
        )


def shutdown() -> None:
    _rt.shutdown_runtime()


def is_initialized() -> bool:
    return _rt.runtime_or_none() is not None


def _ensure_init():
    if _rt.runtime_or_none() is None:
        init()
    return _rt.get_runtime()


def remote(*args, **options):
    """@remote decorator for functions and classes (ref: worker.py:3270 ray.remote)."""

    def decorate(obj):
        if inspect.isclass(obj):
            return ActorClass(obj, options)
        return RemoteFunction(obj, options)

    if len(args) == 1 and callable(args[0]) and not options:
        return decorate(args[0])
    if args:
        raise TypeError("@remote takes keyword options only, e.g. @remote(num_cpus=2)")
    return decorate


def get(refs: Union[ObjectRef, Sequence[ObjectRef]], *, timeout: Optional[float] = None):
    return _ensure_init().get(refs, timeout)


def put(value: Any) -> ObjectRef:
    return _ensure_init().put(value)


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None, fetch_local: bool = True):
    return _ensure_init().wait(refs, num_returns, timeout, fetch_local)


def cancel(ref: ObjectRef, *, force: bool = False) -> None:
    _ensure_init().cancel(ref, force)


def kill(actor: ActorHandle, *, no_restart: bool = True) -> None:
    _ensure_init().kill_actor(actor._ray_actor_id, no_restart)


def get_actor(name: str, namespace: Optional[str] = None) -> ActorHandle:
    runtime = _ensure_init()
    actor_id = runtime.get_named_actor(name, namespace)
    state = runtime.get_actor_state(actor_id)
    return ActorHandle(actor_id, state.spec.cls, state.spec.max_task_retries)


def method(**options):
    """Per-method default options decorator (ref: ray.method)."""

    def decorate(m):
        m._ray_tpu_method_options = options
        return m

    return decorate


def nodes():
    return _ensure_init().nodes()


def cluster_resources() -> Dict[str, float]:
    return _ensure_init().cluster_resources()


def available_resources() -> Dict[str, float]:
    return _ensure_init().available_resources()


def timeline(filename: Optional[str] = None) -> list:
    """Task timeline (ref: _private/state.py:960 ray.timeline).

    With no filename: the raw task-event dicts.  With a filename: writes
    chrome://tracing JSON (load at chrome://tracing / ui.perfetto.dev) and
    returns the chrome-trace event list.
    """
    runtime = _ensure_init()
    if filename is not None:
        from ray_tpu._private import profiling

        return profiling.dump_timeline(filename)
    return runtime.list_task_events()


class _RuntimeContext:
    """(ref: python/ray/runtime_context.py)"""

    @property
    def job_id(self):
        return _ensure_init().job_id

    @property
    def node_id(self):
        return _ensure_init().head_node_id

    def get_task_id(self) -> Optional[str]:
        ctx = _rt.current_task_context()
        return str(ctx.task_id) if ctx else None

    def get_actor_id(self) -> Optional[str]:
        ctx = _rt.current_task_context()
        return str(ctx.actor_id) if ctx and ctx.actor_id else None

    @property
    def was_current_actor_reconstructed(self) -> bool:
        ctx = _rt.current_task_context()
        if not ctx or not ctx.actor_id:
            return False
        state = _ensure_init().get_actor_state(ctx.actor_id)
        return bool(state and state.num_restarts > 0)


def get_runtime_context() -> _RuntimeContext:
    return _RuntimeContext()
