"""XLA collective group — compiled ICI collectives behind the rank-call API.

TPU-native replacement for the reference's NCCL collective group
(ref: python/ray/util/collective/collective_group/nccl_collective_group.py,
830 LoC of cupy-NCCL calls): a group owns a set of JAX devices arranged in a
1-D `jax.sharding.Mesh`; each rank's call contributes its local array, and the
group executes ONE compiled `shard_map` program whose body is the XLA
collective (`psum`, `all_gather`, `psum_scatter`, `ppermute`), riding ICI —
no NCCL, no cupy, no CUDA streams.

Where the reference's ranks rendezvous via a named-actor unique-id store and
then issue runtime NCCL verbs, ranks here rendezvous in-process (threads of
the multi-controller host process) and the "verb" is a cached jitted program
per (op, shape, dtype): the compiler schedules the transfer, overlaps it, and
fuses surrounding elementwise work.  Groups whose ranks are separate OS
processes (jax.distributed) are built as DCNCollectiveGroup instead — same
call surface, ops compiled as global SPMD programs (see dcn_group.py); the
GroupManager picks the tier automatically.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.util import watchdog as _watchdog


def _profiler_record(bucket: str, start: float, end: float) -> None:
    """Attribute an interval to the train step profiler when one is active
    on this thread (each rank's contribute runs on its worker thread).
    Probed via sys.modules — the collective layer must not import the train
    package (the trainer imports collective, not the reverse), and if the
    profiler module was never imported, none can be active."""
    mod = sys.modules.get("ray_tpu.train.profiler")
    if mod is not None:
        mod.record(bucket, start, end)


class ReduceOp:
    SUM = "sum"
    PRODUCT = "product"
    MIN = "min"
    MAX = "max"


class _Rendezvous:
    """Collects one contribution per rank, runs the op once, fans results out.

    The in-process analogue of the reference's NCCL rendezvous (unique-id via
    a named actor, nccl_util.py) — here a barrier across the ranks' threads.
    """

    def __init__(self, world_size: int, timeout_s: float = 300.0):
        self.world_size = world_size
        self.timeout_s = timeout_s
        self.lock = threading.Lock()
        self.slots: Dict[int, Any] = {}
        self.arrivals = 0  # counted at lookup under the group lock
        self.done = threading.Event()
        self.result: Any = None
        self.error: Optional[BaseException] = None

    def contribute(self, rank: int, value: Any, run_fn, participants=None,
                   on_timeout=None) -> Any:
        # Contribute-to-result wall time is this rank's collective-sync
        # cost: waiting for stragglers plus (on the last rank) the compiled
        # op itself — the step profiler's "collective" bucket.  The hang
        # watchdog tracks the same window as a bounded phase: a rank held
        # inside the rendezvous past the stall threshold is a wedge the
        # liveness poll cannot see (the thread is alive, just waiting).
        w0 = time.time()
        _watchdog.phase_enter(f"collective:rank{rank}", "rendezvous")
        try:
            return self._contribute(rank, value, run_fn, participants,
                                    on_timeout)
        finally:
            _watchdog.phase_exit(f"collective:rank{rank}")
            _profiler_record("collective", w0, time.time())

    def _contribute(self, rank: int, value: Any, run_fn, participants=None,
                    on_timeout=None) -> Any:
        members = participants if participants is not None else list(range(self.world_size))
        with self.lock:
            if rank in self.slots:
                raise ValueError(f"rank {rank} contributed twice to collective")
            self.slots[rank] = value
            is_last = len(self.slots) == len(members)
        if is_last:
            try:
                self.result = run_fn({r: self.slots[r] for r in members})
            except BaseException as e:  # noqa: BLE001
                self.error = e
            finally:
                self.done.set()
        else:
            if not self.done.wait(timeout=self.timeout_s):
                # Withdraw our contribution so a retry of this round is clean
                # instead of hitting "contributed twice" on a wedged group.
                with self.lock:
                    self.slots.pop(rank, None)
                if on_timeout is not None:
                    on_timeout(self)
                raise TimeoutError(
                    f"collective rendezvous timed out: {len(self.slots)}/"
                    f"{len(members)} participants arrived")
        if self.error is not None:
            raise self.error
        return self.result


class XLACollectiveGroup:
    def __init__(self, group_name: str, world_size: int,
                 devices: Optional[List[Any]] = None,
                 timeout_s: Optional[float] = None):
        import jax

        from ray_tpu._private.config import GLOBAL_CONFIG

        #: Rendezvous bound: a lost rank fails the OTHERS after this long
        #: instead of holding them hostage (r2 weak #8 — the 300 s constant
        #: was not operator-tunable; elastic trainers want seconds here).
        self.timeout_s = float(timeout_s if timeout_s is not None
                               else GLOBAL_CONFIG.collective_timeout_s)

        all_devices = devices if devices is not None else jax.devices()
        if world_size > len(all_devices):
            # Fewer physical devices than ranks (e.g. 1 real TPU chip, 8-rank
            # group in tests): place multiple ranks per device.  Collectives
            # remain correct but run HOST-SIDE — none of the compiled ICI
            # path is exercised.  Loud, because silently degrading here made
            # 1-chip test hosts "pass" without testing the real programs.
            import warnings

            warnings.warn(
                f"collective group '{group_name}': world_size {world_size} > "
                f"{len(all_devices)} devices — no mesh; ops run host-side, "
                f"the compiled ICI path is NOT exercised",
                RuntimeWarning, stacklevel=2)
            self.devices = [all_devices[i % len(all_devices)] for i in range(world_size)]
            self._oversubscribed = True
        else:
            self.devices = list(all_devices[:world_size])
            self._oversubscribed = False
        self.group_name = group_name
        self.world_size = world_size
        self._mesh = None
        self._compiled: Dict[Tuple, Any] = {}
        self._rendezvous: Dict[Tuple[str, int], _Rendezvous] = {}
        self._rv_lock = threading.Lock()
        self._op_seq: Dict[str, int] = {}

    # ------------------------------------------------------------------ mesh
    def mesh(self):
        """The group's 1-D device mesh (axis name: 'ranks')."""
        import jax

        if self._mesh is None:
            if self._oversubscribed:
                self._mesh = None  # no physical mesh; ops run host-side
            else:
                self._mesh = jax.sharding.Mesh(np.array(self.devices), ("ranks",))
        return self._mesh

    # --------------------------------------------------------------- op cache
    def _get_compiled(self, op_key: Tuple, builder) -> Any:
        fn = self._compiled.get(op_key)
        if fn is None:
            fn = builder()
            self._compiled[op_key] = fn
        return fn

    def _rendezvous_for(self, op: str, n_participants: Optional[int] = None) -> _Rendezvous:
        n = n_participants if n_participants is not None else self.world_size
        with self._rv_lock:
            seq = self._op_seq.get(op, 0)
            key = (op, seq)
            rv = self._rendezvous.get(key)
            if rv is None:
                rv = _Rendezvous(self.world_size, self.timeout_s)
                self._rendezvous[key] = rv
            rv.arrivals += 1
            if rv.arrivals == n:
                # Full round assembled: next lookup starts a fresh round.
                self._op_seq[op] = seq + 1
                self._rendezvous.pop((op, seq - 2), None)  # GC old rounds
            return rv

    def _on_rv_timeout(self, rv: _Rendezvous) -> None:
        with self._rv_lock:
            rv.arrivals = max(0, rv.arrivals - 1)

    # ------------------------------------------------------------ collectives
    def allreduce(self, rank: int, array: Any, op: str = ReduceOp.SUM) -> Any:
        import jax
        import jax.numpy as jnp

        array = jnp.asarray(array)
        rv = self._rendezvous_for(f"allreduce-{op}")

        def run(slots: Dict[int, Any]) -> List[Any]:
            inputs = [slots[r] for r in range(self.world_size)]
            mesh = self.mesh()
            # PRODUCT stays on the host path: the ICI form exp(psum(log)) is
            # wrong for negative/zero inputs.
            if mesh is None or op == ReduceOp.PRODUCT:
                stacked = jnp.stack(inputs)
                out = _host_reduce(stacked, op)
                return [out] * self.world_size
            key = ("allreduce", op, inputs[0].shape, str(inputs[0].dtype))

            def build():
                from jax.sharding import PartitionSpec as P

                def body(x):
                    # x: (1, *shape) per rank — reduce over the mesh axis.
                    return _lax_reduce(x, op, "ranks")

                return jax.jit(
                    jax.shard_map(
                        body, mesh=mesh,
                        in_specs=P("ranks"), out_specs=P("ranks"),
                    )
                )

            fn = self._get_compiled(key, build)
            out = fn(self._mesh_put(jnp.stack(inputs)))
            return [out[i] for i in range(self.world_size)]

        results = rv.contribute(rank, array, run, on_timeout=self._on_rv_timeout)
        return results[rank]

    def _mesh_put(self, stacked):
        import jax

        return jax.device_put(
            stacked,
            jax.sharding.NamedSharding(
                self.mesh(), jax.sharding.PartitionSpec("ranks")))

    def allgather(self, rank: int, array: Any) -> Any:
        import jax
        import jax.numpy as jnp

        array = jnp.asarray(array)
        rv = self._rendezvous_for("allgather")

        def run(slots: Dict[int, Any]) -> List[Any]:
            inputs = [slots[r] for r in range(self.world_size)]
            mesh = self.mesh()
            if mesh is None:
                out = jnp.stack(inputs)
                return [out] * self.world_size
            key = ("allgather", inputs[0].shape, str(inputs[0].dtype))

            def build():
                from jax import lax
                from jax.sharding import PartitionSpec as P

                def body(x):
                    # x: (1, *shape) per-rank block; gather the full stack —
                    # identical on every rank, so the output is replicated.
                    return lax.all_gather(x, "ranks", axis=0, tiled=True)

                # check_vma=False: the gather output is replicated by
                # construction, which the static VMA check cannot infer.
                return jax.jit(jax.shard_map(
                    body, mesh=mesh, in_specs=P("ranks"), out_specs=P(),
                    check_vma=False))

            fn = self._get_compiled(key, build)
            out = fn(self._mesh_put(jnp.stack(inputs)))
            return [out] * self.world_size

        results = rv.contribute(rank, array, run, on_timeout=self._on_rv_timeout)
        return results[rank]

    def reducescatter(self, rank: int, array: Any, op: str = ReduceOp.SUM) -> Any:
        """Each rank contributes shape (world, ...); receives its reduced shard."""
        import jax
        import jax.numpy as jnp

        array = jnp.asarray(array)
        if array.shape[0] != self.world_size:
            raise ValueError(
                f"reducescatter input dim0 ({array.shape[0]}) must equal world_size "
                f"({self.world_size})")
        rv = self._rendezvous_for(f"reducescatter-{op}")

        def run(slots: Dict[int, Any]) -> List[Any]:
            inputs = [slots[r] for r in range(self.world_size)]
            mesh = self.mesh()
            if mesh is None or op == ReduceOp.PRODUCT:
                stacked = jnp.stack(inputs)
                reduced = _host_reduce(stacked, op)  # (world, ...)
                return [reduced[i] for i in range(self.world_size)]
            key = ("reducescatter", op, inputs[0].shape, str(inputs[0].dtype))

            def build():
                from jax import lax
                from jax.sharding import PartitionSpec as P

                def body(x):
                    # x: (1, world, *shape) — this rank's full contribution.
                    y = x[0]
                    if op == ReduceOp.SUM:
                        return lax.psum_scatter(
                            y, "ranks", scatter_dimension=0, tiled=True)
                    # No pmax/pmin-scatter primitive: reduce then keep our row.
                    reduced = _lax_reduce(y, op, "ranks")
                    idx = lax.axis_index("ranks")
                    return lax.dynamic_slice_in_dim(reduced, idx, 1, axis=0)

                return jax.jit(jax.shard_map(
                    body, mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks")))

            fn = self._get_compiled(key, build)
            out = fn(self._mesh_put(jnp.stack(inputs)))  # (world, *shape)
            return [out[i] for i in range(self.world_size)]

        results = rv.contribute(rank, array, run, on_timeout=self._on_rv_timeout)
        return results[rank]

    def broadcast(self, rank: int, array: Any, src_rank: int = 0) -> Any:
        import jax
        import jax.numpy as jnp

        array = jnp.asarray(array)
        rv = self._rendezvous_for(f"broadcast-{src_rank}")

        def run(slots: Dict[int, Any]) -> List[Any]:
            mesh = self.mesh()
            if mesh is None:
                return [slots[src_rank]] * self.world_size
            inputs = [slots[r] for r in range(self.world_size)]
            key = ("broadcast", src_rank, inputs[0].shape, str(inputs[0].dtype))

            def build():
                from jax import lax
                from jax.sharding import PartitionSpec as P

                def body(x):
                    # Mask all but src's block, then psum — the select+psum
                    # lowering of broadcast (one ICI reduction, replicated out).
                    idx = lax.axis_index("ranks")
                    contrib = jnp.where(idx == src_rank, x, jnp.zeros_like(x))
                    return lax.psum(contrib, "ranks")

                # check_vma=False: psum output is replicated by construction.
                return jax.jit(jax.shard_map(
                    body, mesh=mesh, in_specs=P("ranks"), out_specs=P(),
                    check_vma=False))

            fn = self._get_compiled(key, build)
            out = fn(self._mesh_put(jnp.stack(inputs)))  # (1, *shape) replicated
            return [out[0]] * self.world_size

        results = rv.contribute(rank, array, run, on_timeout=self._on_rv_timeout)
        return results[rank]

    def barrier(self, rank: int) -> None:
        rv = self._rendezvous_for("barrier")
        rv.contribute(rank, 0, lambda slots: [None] * self.world_size,
                      on_timeout=self._on_rv_timeout)

    def send_recv(self, rank: int, array: Any, perm: List[Tuple[int, int]]) -> Any:
        """ppermute-style paired send/recv: perm is [(src, dst), ...].

        Replaces the reference's point-to-point NCCL send/recv
        (collective.py:531,594) with a single collective-permute program —
        the idiomatic ICI form (neighbor exchange rides the ring).
        """
        import jax.numpy as jnp

        array = jnp.asarray(array)
        # Only the ranks named in perm participate — a 2-party send/recv in an
        # 8-rank group must not wait for the other 6.
        participants = sorted({r for pair in perm for r in pair})
        if rank not in participants:
            raise ValueError(f"rank {rank} is not part of perm {perm}")
        rv = self._rendezvous_for(f"sendrecv-{tuple(perm)}", n_participants=len(participants))

        def run(slots: Dict[int, Any]) -> Dict[int, Any]:
            import jax

            template = next(iter(slots.values()))
            mesh = self.mesh()
            if mesh is None:
                out = {r: jnp.zeros_like(template) for r in participants}
                for src, dst in perm:
                    out[dst] = slots[src]
                return out
            # Non-participants contribute zeros; ppermute's non-receivers get
            # zeros back, matching the host-path semantics.
            inputs = [slots.get(r, jnp.zeros_like(template))
                      for r in range(self.world_size)]
            key = ("sendrecv", tuple(perm), template.shape, str(template.dtype))

            def build():
                from jax import lax
                from jax.sharding import PartitionSpec as P

                def body(x):
                    # The promised single collective-permute program: blocks
                    # move src->dst along the ring in one compiled op.
                    return lax.ppermute(x, "ranks", perm)

                return jax.jit(jax.shard_map(
                    body, mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks")))

            fn = self._get_compiled(key, build)
            out = fn(self._mesh_put(jnp.stack(inputs)))
            return {r: out[r] for r in participants}

        results = rv.contribute(rank, array, run, participants=participants,
                                on_timeout=self._on_rv_timeout)
        return results[rank]

    def destroy(self) -> None:
        # Poison in-flight rounds so blocked participants wake immediately
        # instead of sitting out the 300s rendezvous timeout (matters for
        # elastic restart: the controller destroys the group on failure).
        with self._rv_lock:
            rvs = list(self._rendezvous.values())
            self._rendezvous.clear()
        for rv in rvs:
            if not rv.done.is_set():
                rv.error = RuntimeError(
                    f"collective group '{self.group_name}' was destroyed")
                rv.done.set()
        self._compiled.clear()


def _lax_reduce(x, op: str, axis_name: str):
    from jax import lax

    if op == ReduceOp.SUM:
        return lax.psum(x, axis_name)
    if op == ReduceOp.MAX:
        return lax.pmax(x, axis_name)
    if op == ReduceOp.MIN:
        return lax.pmin(x, axis_name)
    if op == ReduceOp.PRODUCT:
        import jax.numpy as jnp

        return jnp.exp(lax.psum(jnp.log(x), axis_name))
    raise ValueError(f"Unknown reduce op: {op}")


def _host_reduce(stacked, op: str):
    import jax.numpy as jnp

    if op == ReduceOp.SUM:
        return jnp.sum(stacked, axis=0)
    if op == ReduceOp.MAX:
        return jnp.max(stacked, axis=0)
    if op == ReduceOp.MIN:
        return jnp.min(stacked, axis=0)
    if op == ReduceOp.PRODUCT:
        return jnp.prod(stacked, axis=0)
    raise ValueError(f"Unknown reduce op: {op}")
