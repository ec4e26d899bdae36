"""DCN-tier collective group — multi-controller SPMD collectives.

When the ranks of a collective group are separate OS processes joined via
``jax.distributed`` (one rank per process — the multi-host trainer layout),
the in-process rendezvous of ``XLACollectiveGroup`` cannot see the other
ranks.  This group instead runs every op as the SAME compiled SPMD program on
every process: each rank's contribution becomes its process-local shard of a
global array (``jax.make_array_from_process_local_data``) and the op body is
a ``shard_map`` collective (`psum`, `all_gather`, `psum_scatter`,
`ppermute`) over a 1-D ``ranks`` mesh spanning one device per process — XLA
schedules the transfer over ICI within a slice and DCN across hosts.

This is the TPU-native replacement for the reference's *cross-host* backends
(ref: python/ray/util/collective/collective_group/nccl_collective_group.py
multi-node NCCL groups; gloo_collective_group.py CPU tier): no NCCL
communicators, no gloo contexts — one compiled program per (op, shape,
dtype), the same program single-host groups use, just over a multi-process
device set.

SPMD contract (differs from the thread-tier group): every rank must issue
the SAME sequence of collective calls — these are global programs, so a rank
that skips a call deadlocks the others, exactly like raw `jax.distributed`
(and exactly like NCCL).  The exception is ``send_recv``, which moves host
bytes through the jax.distributed KV store so 2-party exchanges don't need
the full group; on TPU the performant path for p2p pipelines is `ppermute`
inside your own jitted step, not this op.
"""

from __future__ import annotations

import base64
import pickle
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.collective.xla_group import ReduceOp, _lax_reduce


def multiprocess_world() -> int:
    """Number of jax.distributed processes, 0 if not a multi-process run.

    Reads jax's distributed global state WITHOUT touching the backend (so
    calling this never triggers device initialization)."""
    try:
        from jax._src import distributed as jdist

        state = jdist.global_state
        if state.client is None:
            return 0
        return int(state.num_processes or 0)
    except Exception:  # pragma: no cover - jax internals moved
        return 0


def kv_client():
    from jax._src import distributed as jdist

    client = jdist.global_state.client
    if client is None:
        raise RuntimeError("jax.distributed is not initialized")
    return client


class DCNCollectiveGroup:
    """One collective group across jax.distributed processes.

    Mirrors XLACollectiveGroup's (rank, array) call surface so
    ``ray_tpu.collective.*`` works unchanged in multi-host trainer workers.
    """

    def __init__(self, group_name: str, world_size: int,
                 devices: Optional[List[Any]] = None,
                 timeout_s: Optional[float] = None):
        import jax

        from ray_tpu._private.config import GLOBAL_CONFIG

        self.group_name = group_name
        self.world_size = world_size
        self.timeout_s = float(timeout_s if timeout_s is not None
                               else GLOBAL_CONFIG.collective_timeout_s)
        nproc = jax.process_count()
        if world_size != nproc:
            raise ValueError(
                f"multi-process collective group '{group_name}': world_size "
                f"{world_size} must equal jax.process_count() {nproc} (one "
                f"rank per process; for multiple ranks in one process use "
                f"the in-process tier)")
        # One device per process, ordered by process index — the 'ranks' axis.
        per_proc: Dict[int, Any] = {}
        for d in sorted(jax.devices(), key=lambda d: (d.process_index, d.id)):
            per_proc.setdefault(d.process_index, d)
        self.devices = [per_proc[i] for i in range(world_size)]
        self._mesh = jax.sharding.Mesh(np.array(self.devices), ("ranks",))
        self._compiled: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()
        self._p2p_seq: Dict[Tuple, int] = {}

    # ------------------------------------------------------------ helpers
    def _check_rank(self, rank: int) -> None:
        import jax

        if rank != jax.process_index():
            raise ValueError(
                f"rank {rank} called a DCN collective from process "
                f"{jax.process_index()} — in multi-process groups the rank IS "
                f"the process index (one rank per process)")

    def _global(self, local_block: np.ndarray):
        """This process's (1, *shape) block as a (world, *shape) global array."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(self._mesh, P("ranks"))
        global_shape = (self.world_size,) + tuple(local_block.shape[1:])
        return jax.make_array_from_process_local_data(
            sharding, local_block, global_shape)

    def _get_compiled(self, key: Tuple, builder):
        with self._lock:
            fn = self._compiled.get(key)
            if fn is None:
                fn = builder()
                self._compiled[key] = fn
            return fn

    @staticmethod
    def _local(out) -> np.ndarray:
        """This process's shard of a mesh-sharded output."""
        return np.asarray(out.addressable_shards[0].data)

    # --------------------------------------------------------- collectives
    def allreduce(self, rank: int, array: Any, op: str = ReduceOp.SUM) -> Any:
        import jax
        from jax.sharding import PartitionSpec as P

        self._check_rank(rank)
        if op == ReduceOp.PRODUCT:
            # exp(psum(log)) is wrong for negative/zero inputs — gather and
            # reduce host-side (same policy as the in-process group).
            stacked = self.allgather(rank, array)
            return np.prod(np.asarray(stacked), axis=0)
        x = np.asarray(array)[None]
        key = ("allreduce", op, x.shape, str(x.dtype))

        def build():
            return jax.jit(jax.shard_map(
                lambda b: _lax_reduce(b, op, "ranks"), mesh=self._mesh,
                in_specs=P("ranks"), out_specs=P("ranks")))

        out = self._get_compiled(key, build)(self._global(x))
        return self._local(out)[0]

    def allgather(self, rank: int, array: Any) -> Any:
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        self._check_rank(rank)
        x = np.asarray(array)[None]
        key = ("allgather", x.shape, str(x.dtype))

        def build():
            # check_vma=False: the gathered output is replicated by
            # construction, which the static VMA check cannot infer.
            return jax.jit(jax.shard_map(
                lambda b: lax.all_gather(b, "ranks", axis=0, tiled=True),
                mesh=self._mesh, in_specs=P("ranks"), out_specs=P(),
                check_vma=False))

        out = self._get_compiled(key, build)(self._global(x))
        return self._local(out)  # replicated: local copy is the full stack

    def reducescatter(self, rank: int, array: Any, op: str = ReduceOp.SUM) -> Any:
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        self._check_rank(rank)
        x = np.asarray(array)
        if x.shape[0] != self.world_size:
            raise ValueError(
                f"reducescatter input dim0 ({x.shape[0]}) must equal "
                f"world_size ({self.world_size})")
        if op == ReduceOp.PRODUCT:
            stacked = self.allgather(rank, x)  # (world, world, *s)
            return np.prod(np.asarray(stacked), axis=0)[rank]
        x = x[None]  # (1, world, *s): this rank's full contribution
        key = ("reducescatter", op, x.shape, str(x.dtype))

        def build():
            def body(b):
                y = b[0]  # (world, *s)
                if op == ReduceOp.SUM:
                    return lax.psum_scatter(
                        y, "ranks", scatter_dimension=0, tiled=True)
                reduced = _lax_reduce(y, op, "ranks")
                idx = lax.axis_index("ranks")
                return lax.dynamic_slice_in_dim(reduced, idx, 1, axis=0)

            return jax.jit(jax.shard_map(
                body, mesh=self._mesh, in_specs=P("ranks"),
                out_specs=P("ranks")))

        out = self._get_compiled(key, build)(self._global(x))
        return self._local(out)[0]

    def broadcast(self, rank: int, array: Any, src_rank: int = 0) -> Any:
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        self._check_rank(rank)
        x = np.asarray(array)[None]
        key = ("broadcast", src_rank, x.shape, str(x.dtype))

        def build():
            def body(b):
                idx = lax.axis_index("ranks")
                contrib = jnp.where(idx == src_rank, b, jnp.zeros_like(b))
                return lax.psum(contrib, "ranks")

            return jax.jit(jax.shard_map(
                body, mesh=self._mesh, in_specs=P("ranks"), out_specs=P(),
                check_vma=False))

        out = self._get_compiled(key, build)(self._global(x))
        return self._local(out)[0]

    def barrier(self, rank: int) -> None:
        self.allreduce(rank, np.zeros((1,), np.float32))

    # ---------------------------------------------------------------- p2p
    def send_recv(self, rank: int, array: Any, perm: List[Tuple[int, int]]) -> Any:
        """Point-to-point exchange through the jax.distributed KV store.

        Host-side by design: only the ranks named in ``perm`` participate, so
        a compiled global program (which needs every process) cannot express
        it.  Bulk p2p on TPU belongs inside jitted steps as `ppermute`; this
        op exists for control-plane exchanges (ref: collective.py:531 send /
        :594 recv semantics)."""
        self._check_rank(rank)
        participants = sorted({r for pair in perm for r in pair})
        if rank not in participants:
            raise ValueError(f"rank {rank} is not part of perm {perm}")
        client = kv_client()
        timeout_ms = int(self.timeout_s * 1000)
        out: Any = np.zeros_like(np.asarray(array))
        for src, dst in perm:
            with self._lock:
                seq = self._p2p_seq.get((src, dst), 0)
                self._p2p_seq[(src, dst)] = seq + 1
            key = f"ray_tpu/{self.group_name}/p2p/{src}-{dst}/{seq}"
            if rank == src:
                payload = base64.b64encode(
                    pickle.dumps(np.asarray(array))).decode()
                client.key_value_set(key, payload)
            if rank == dst:
                payload = client.blocking_key_value_get(key, timeout_ms)
                out = pickle.loads(base64.b64decode(payload))
                try:
                    client.key_value_delete(key)
                except Exception:
                    pass
        return out

    def destroy(self) -> None:
        self._compiled.clear()
