"""Trainers + the training controller.

Modeled on the reference's Train v2 architecture (ref: python/ray/train/v2/
_internal/execution/controller.py:73 TrainController — a standalone control
loop polling a WorkerGroup, with ScalingPolicy/FailurePolicy), rather than
Train v1's route through a single-trial Tune run (ref: base_trainer.py:608).
Workers are actors (ref: _internal/worker_group.py:102 WorkerGroup,
RayTrainWorker:19); on a TPU host they are thread actors sharing the one JAX
client, and gradient sync happens either through ray_tpu.collective (SPMD
mode) or inside a pjit'd step the user writes against the mesh (mesh mode).

Elastic recovery (ref: v2 FailurePolicy): a worker failure tears down the
group, and the whole group restarts from the latest registered checkpoint —
delivered to workers via train.get_checkpoint().  With
``ScalingConfig(elastic=ElasticConfig(...))`` the world size itself is
dynamic: a preemption shrinks the group to surviving capacity, restores the
last committed step from the in-memory replica tier (disk as the floor),
reshards the data through the exactly-once sample ledger
(train/elastic.py) and resumes inside the same fit(); when capacity comes
back the group grows again at the next checkpoint boundary
(docs/elastic-training.md).

NOTE on thread workers + JAX: calls into *jitted* functions are thread-safe
and release the GIL; concurrent *eager* jax ops from many worker threads can
race inside jax's dispatch on some backends.  Keep per-step math inside jit
(which you want for performance anyway) — see tests/test_train.py
test_multi_worker_allreduce_training for the pattern.
"""

from __future__ import annotations

import queue
import tempfile
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu import collective
from ray_tpu._private import fault_injection
from ray_tpu.exceptions import RayTpuError, TaskError
from ray_tpu.train import metrics as train_metrics
from ray_tpu.train import run_registry
from ray_tpu.train.checkpoint import Checkpoint, CheckpointManager
from ray_tpu.train.config import DatasetConfig, RunConfig, ScalingConfig
from ray_tpu.train.elastic import ElasticDatasetShard, SampleLedger
from ray_tpu.train.profiler import StepProfiler
from ray_tpu.train.session import TrainContext, TrainSession, clear_session, init_session
from ray_tpu.util import device_telemetry, flight_recorder, tracing
from ray_tpu.util.placement_group import (
    PlacementGroupSchedulingStrategy,
    placement_group,
    remove_placement_group,
)


class Result:
    """(ref: python/ray/train/result.py Result)"""

    def __init__(self, metrics: Optional[Dict[str, Any]], checkpoint: Optional[Checkpoint],
                 path: str, error: Optional[BaseException] = None,
                 metrics_history: Optional[List[Dict[str, Any]]] = None,
                 elastic_events: Optional[List[Dict[str, Any]]] = None):
        self.metrics = metrics
        self.checkpoint = checkpoint
        self.path = path
        self.error = error
        self.metrics_history = metrics_history or []
        #: shrink/grow/recovery records from elastic training (empty unless
        #: ScalingConfig.elastic): type, from_world/to_world, restore_step,
        #: lost_steps, requeued_samples, recovery_seconds.
        self.elastic_events = elastic_events or []

    def __repr__(self) -> str:
        return f"Result(metrics={self.metrics}, checkpoint={self.checkpoint}, error={self.error})"


def invoke_train_loop(train_loop: Callable,
                      loop_config: Optional[Dict[str, Any]]) -> None:
    """Signature-dispatch shared by every worker kind (ref: the reference
    accepts both `def loop()` and `def loop(config)`)."""
    import inspect

    sig = inspect.signature(train_loop)
    if len(sig.parameters) >= 1:
        train_loop(loop_config or {})
    else:
        train_loop()


@ray_tpu.remote
class TrainWorker:
    """(ref: _internal/worker_group.py:19 RayTrainWorker)"""

    def __init__(self, rank: int, world_size: int, group_name: str):
        self.rank = rank
        self.world_size = world_size
        # Multi-host: join the jax.distributed cluster when the operator set
        # RAY_TPU_COORDINATOR/... on the worker env (the DCN-tier bootstrap;
        # ref: train/torch/config.py:66 _setup_torch_process_group).
        from ray_tpu.collective import distributed

        distributed.auto_initialize()
        collective.init_collective_group(world_size, rank, backend="xla",
                                         group_name=group_name)

    def run(self, train_loop: Callable, loop_config: Optional[Dict[str, Any]],
            session: TrainSession) -> str:
        # Chaos: a worker dying right at run entry (the other half of the
        # per-report() consultation in TrainSession.report).
        try:
            fault_injection.check("train_worker_run")
            init_session(session)
        finally:
            # The loop function is entered now or never: ``fit()``'s
            # ``train.fit_setup`` span waits for this.
            session.loop_entered.set()
        try:
            invoke_train_loop(train_loop, loop_config)
            return "done"
        except StopIteration:
            return "stopped"
        finally:
            clear_session()


def _node_ip() -> str:
    """Best-effort routable IP of this host (ref: ray._private.services
    get_node_ip_address — UDP-connect trick, no packets sent)."""
    import socket

    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            s.connect(("8.8.8.8", 80))
            return s.getsockname()[0]
        finally:
            s.close()
    except OSError:
        return "127.0.0.1"


def _reserve_addr() -> str:
    """Probe a free port on this host and return "ip:port" — the rendezvous
    address a rank-0 worker advertises (jax.distributed coordinator / gloo
    master).  TOCTOU-racy by nature; the consumers bound their rendezvous
    with timeouts for exactly that reason."""
    import socket

    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return f"{_node_ip()}:{port}"


def _drive_worker_refs(refs, drain) -> None:
    """Poll a worker group's run() refs to completion, draining the report
    channel as results stream in; re-raises the first worker error (shared
    by the process-tier controllers — torch and jax.distributed)."""
    pending = list(refs)
    while pending:
        ready, pending = ray_tpu.wait(pending, num_returns=len(pending),
                                      timeout=0.05)
        drain()
        for r in ready:
            ray_tpu.get(r)  # raise worker errors here
    drain()


class DistTrainSession:
    """Pickle-safe session for multi-host workers: report() ships metrics +
    the checkpoint directory BY VALUE (tar.gz) through an actor-backed queue
    — worker processes live on other machines, so neither the thread tier's
    in-memory queue nor bare paths can cross (ref: _TrainSession:112
    contract; train/_internal/storage.py checkpoint upload)."""

    def __init__(self, context: TrainContext, report_queue,
                 checkpoint_to_restore: Optional[Checkpoint] = None):
        self.context = context
        self._queue = report_queue
        self.checkpoint_to_restore = checkpoint_to_restore
        self.dataset_shards: Dict[str, Any] = {}
        self.stop_requested = threading.Event()

    def report(self, metrics: Dict[str, Any],
               checkpoint: Optional[Checkpoint] = None) -> None:
        from ray_tpu.train.checkpoint import pack_checkpoint

        self._queue.put({
            "rank": self.context.world_rank,
            "metrics": dict(metrics),
            "checkpoint_blob": pack_checkpoint(checkpoint),
        })


@ray_tpu.remote
class JaxDistTrainWorker:
    """One jax.distributed rank in its own OS process.

    The multi-host worker tier (ref: _internal/backend_executor.py:69 — the
    worker group's actors span nodes and are bootstrapped into one process
    group; train/torch/config.py:66,115 _setup_torch_process_group).  Here
    the process group is JAX's multi-controller runtime: after setup(),
    jax.devices() on every worker is the GLOBAL device set, meshes span the
    cluster, and ray_tpu.collective ops compile to global SPMD programs
    (collective/dcn_group.py).  Always created with isolation='process'."""

    def __init__(self, rank: int, world_size: int, group_name: str):
        self.rank = rank
        self.world = world_size
        self.group_name = group_name

    def reserve_coordinator(self) -> str:
        """Rank 0 picks the jax.distributed coordinator address on ITS host."""
        return _reserve_addr()

    def setup(self, coordinator: str) -> Dict[str, Any]:
        """Join the multi-controller cluster; returns topology for sanity
        checks.  Called CONCURRENTLY on all ranks (initialize barriers)."""
        from ray_tpu.collective import distributed
        from ray_tpu.parallel.compile_cache import configure_compile_cache

        configure_compile_cache()
        distributed.initialize(coordinator, self.world, self.rank)
        collective.init_collective_group(self.world, self.rank, backend="xla",
                                         group_name=self.group_name)
        import jax

        return {"rank": self.rank, "process_count": jax.process_count(),
                "global_devices": len(jax.devices())}

    def run(self, train_loop: Callable, loop_config: Optional[Dict[str, Any]],
            context: TrainContext, report_queue,
            restore_blob: Optional[bytes]) -> str:
        import shutil

        from ray_tpu.train.checkpoint import unpack_checkpoint

        restore = unpack_checkpoint(restore_blob)
        session = DistTrainSession(context, report_queue, restore)
        init_session(session)
        try:
            invoke_train_loop(train_loop, loop_config)
            return "done"
        finally:
            clear_session()
            if restore is not None:
                # The unpacked restore dir is this attempt's scratch copy —
                # N workers x N restarts of model-sized leaks otherwise.
                shutil.rmtree(restore.path, ignore_errors=True)

    def teardown(self) -> None:
        collective.destroy_collective_group(self.group_name)
        from ray_tpu.collective import distributed

        distributed.shutdown()


class DataParallelTrainer:
    """(ref: python/ray/train/data_parallel_trainer.py:25)"""

    _collective_counter = 0

    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
        dataset_config: Optional[DatasetConfig] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
    ):
        self.train_loop = train_loop_per_worker
        self.train_loop_config = train_loop_config
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        self.dataset_config = dataset_config or DatasetConfig()
        self.resume_from_checkpoint = resume_from_checkpoint
        # Elastic recovery clock: set at failure/grow time, observed by
        # _drain_sessions when the first report of the resumed attempt
        # lands (kill -> training-resumed latency).
        self._recovery_t0: Optional[float] = None
        self._recovery_event: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------------------ fit
    def fit(self) -> Result:
        """Run the training; returns a :class:`Result` (a failure is its
        ``error``).  From here to the moment the first worker enters the
        loop function (placement group, workers, dataset split, sessions)
        is the span ``train.fit_setup``, a row of the set-up's account
        (``device_telemetry.setup_account``); with process-tier workers,
        whose loop this process cannot see, to their ``run`` calls."""
        self._fit_setup = device_telemetry.setup_span(
            "train.fit_setup", {"workers": self.scaling_config.num_workers})
        with self._fit_setup:  # _run_with_pg ends it early
            return self._fit()

    def _fit(self) -> Result:
        if not ray_tpu.is_initialized():
            ray_tpu.init()
        run_name = self.run_config.name or f"train_{int(time.time())}"
        storage = self.run_config.storage_path or tempfile.mkdtemp(prefix="ray_tpu_train_")
        import os

        experiment_path = os.path.join(storage, run_name)
        ckpt_conf = self.run_config.checkpoint_config
        manager = CheckpointManager(
            os.path.join(experiment_path, "checkpoints"),
            num_to_keep=ckpt_conf.num_to_keep,
            score_attribute=ckpt_conf.checkpoint_score_attribute,
            score_order=ckpt_conf.checkpoint_score_order,
        )

        # Async checkpointing (CheckpointConfig.async_save): a
        # CheckpointCoordinator actor owns the same checkpoints dir and
        # two-phase-commits sharded saves flowing out of report(checkpoint=
        # <pytree>); restarts restore from its latest committed step.
        coordinator = None
        if ckpt_conf.async_save:
            from ray_tpu._private.runtime import get_runtime
            from ray_tpu.checkpoint import CheckpointCoordinator
            from ray_tpu.util.scheduling_strategies import (
                NodeAffinitySchedulingStrategy,
            )

            # The coordinator owns its own subdirectory: it and the legacy
            # CheckpointManager assign checkpoint_NNNNNN names from
            # independent counters, so sharing one directory would let
            # either side clobber or retention-delete the other's dirs.
            # Pinned to the head node (where this controller lives): a
            # preempted worker node must never take the commit authority
            # with it — elastic recovery asks it what step to restore.
            coordinator = ray_tpu.remote(CheckpointCoordinator).options(
                scheduling_strategy=NodeAffinitySchedulingStrategy(
                    str(get_runtime().head_node_id), soft=True),
            ).remote(
                os.path.join(experiment_path, "checkpoints", "sharded"),
                keep=ckpt_conf.num_to_keep,
                replica_steps=ckpt_conf.replica_memory_steps)

        scfg = self.scaling_config
        elastic = scfg.elastic
        cur_world = scfg.num_workers
        elastic_events: List[Dict[str, Any]] = []
        # State API: the run is visible to list_train_runs() (and the
        # /api/train_runs route) for its whole lifetime — world size,
        # committed step and elastic events are kept current below.
        run_registry.register_run(run_name, world_size=cur_world,
                                  target_world=cur_world,
                                  path=experiment_path,
                                  elastic=elastic is not None)
        attempt_no = 1
        self._recovery_t0 = None
        self._recovery_event = None
        # Elastic data plane: every sized dataset becomes a shared
        # exactly-once ledger that outlives individual attempts — exclusive
        # claiming IS the reshard (see train/elastic.py).  Streaming
        # datasets keep the legacy per-world split.
        ledgers: Dict[str, SampleLedger] = {}
        if elastic is not None:
            for name, ds in self.datasets.items():
                if (not hasattr(ds, "streaming_split")
                        and hasattr(ds, "__len__")
                        and hasattr(ds, "__getitem__")):
                    ledgers[name] = SampleLedger(
                        ds, seal_on_claim=coordinator is None)
        #: exposed for inspection (chaos tests assert the per-sample
        #: exactly-once ledger after fit() returns)
        self.sample_ledgers = ledgers
        # Streaming data plane (docs/data-ingestion.md): with
        # DatasetConfig(streaming=True) — the default — every lazy Dataset
        # becomes a StreamingIngest shared across attempts: workers claim
        # source shards through a per-epoch ledger (claiming IS the
        # resplit under elastic world changes) and stream them through
        # backpressure -> windowed shuffle -> rebatch -> prefetch.
        dcfg = self.dataset_config
        ingests: Dict[str, Any] = {}
        if dcfg.streaming:
            from ray_tpu.data.ingest import StreamingIngest

            for name, ds in self.datasets.items():
                if name not in ledgers and hasattr(ds, "_op"):
                    ingests[name] = StreamingIngest(
                        ds,
                        window_blocks=dcfg.shuffle_window_blocks,
                        window_bytes=dcfg.window_bytes,
                        seed=dcfg.shuffle_seed,
                        prefetch_batches=dcfg.prefetch_batches,
                        seal_on_claim=coordinator is None)
        #: exposed for inspection (tests audit per-shard exactly-once
        #: accounting after fit() returns)
        self.streaming_ingests = ingests

        max_failures = self.run_config.failure_config.max_failures
        failures = 0
        restore_ckpt = self.resume_from_checkpoint
        last_restore_step: Optional[int] = None
        last_error: Optional[BaseException] = None
        history: List[Dict[str, Any]] = []

        try:
            while True:
                outcome = self._run_attempt(run_name, manager, restore_ckpt,
                                            experiment_path, coordinator,
                                            world=cur_world, ledgers=ledgers,
                                            ingests=ingests)
                history.extend(outcome["history"])
                if outcome["status"] == "finished":
                    run_registry.finish_run(run_name, "finished")
                    for ledger in ledgers.values():
                        ledger.seal_all()  # clean finish: nothing rolls back
                    for ingest in ingests.values():
                        # Not seal_all: shard claims the prefetch pump made
                        # but whose batches the user loop never consumed
                        # (a fixed-steps loop breaking out of iter_batches)
                        # roll back so audit() never reports them trained.
                        ingest.finish()
                    return Result(
                        metrics=outcome["last_metrics"],
                        checkpoint=(manager.latest_checkpoint()
                                    or self._coordinator_checkpoint(
                                        coordinator, from_memory=False)),
                        path=experiment_path,
                        # Surfaces e.g. "every async save failed": training
                        # succeeded but the run has no usable checkpoint.
                        error=outcome["error"],
                        metrics_history=history,
                        elastic_events=elastic_events,
                    )
                if outcome["status"] == "grow":
                    # Capacity came back and every worker stopped cleanly at
                    # a checkpoint boundary: restore from the committed step
                    # (its save drained before we got here) and restart the
                    # attempt at the bigger world.  Not a failure.
                    new_world = outcome["new_world"]
                    restore_ckpt, step = self._elastic_restore_point(
                        coordinator, manager)
                    for ledger in ledgers.values():
                        ledger.rollback(step)
                    for ingest in ingests.values():
                        ingest.rollback(step)
                    train_metrics.GROW_EVENTS.inc()
                    event = {"type": "grow", "from_world": cur_world,
                             "to_world": new_world, "restore_step": step,
                             "time": time.time()}
                    elastic_events.append(event)
                    run_registry.record_event(run_name, event)
                    self._recovery_t0 = time.monotonic()
                    self._recovery_event = event
                    cur_world = new_world
                    attempt_no += 1
                    run_registry.update_run(run_name, attempts=attempt_no)
                    if step is not None:
                        last_restore_step = step
                    continue
                last_error = outcome["error"]
                fatal = outcome["status"] == "fatal"
                handled = False
                if not fatal and elastic is not None:
                    from ray_tpu.autoscaler.elastic import worker_capacity

                    # Shrink (or hold) the world to what the live cluster
                    # can host, restore the last committed step — memory
                    # replicas first — and requeue every rolled-back claim.
                    cap = worker_capacity(scfg.worker_resources())
                    target = max(elastic.min_workers,
                                 min(cap, elastic.resolve_max(scfg.num_workers)))
                    restore_ckpt, step = self._elastic_restore_point(
                        coordinator, manager)
                    if restore_ckpt is None:
                        restore_ckpt = self.resume_from_checkpoint
                    requeued = sum(ledger.rollback(step)
                                   for ledger in ledgers.values())
                    requeued += sum(ingest.rollback(step)
                                    for ingest in ingests.values())
                    last_step = outcome.get("last_step")
                    lost = 0
                    if last_step is not None:
                        lost = max(0, last_step
                                   - (step if step is not None else -1))
                    train_metrics.LOST_STEPS.inc(lost)  # inc(0) is a no-op
                    if target < cur_world:
                        train_metrics.SHRINK_EVENTS.inc()
                    event = {"type": "shrink" if target < cur_world else "recover",
                             "from_world": cur_world, "to_world": target,
                             "restore_step": step, "lost_steps": lost,
                             "requeued_samples": requeued, "time": time.time()}
                    elastic_events.append(event)
                    run_registry.record_event(run_name, event)
                    # Preemption forensics: snapshot the black box before
                    # the recovery attempt overwrites the ring — the dump
                    # carries the failed attempt's final train spans and
                    # every thread's stack (best-effort, flood-controlled).
                    flight_recorder.trigger_dump("elastic_preempt", {
                        "run": run_name, "event": event,
                        "error": str(last_error) if last_error else "",
                    })
                    self._recovery_t0 = outcome.get("failed_at") or time.monotonic()
                    self._recovery_event = event
                    cur_world = target
                    # A recovery only "handles" the failure when the cluster
                    # can still run AND the restore point advanced since the
                    # last one — repeated failures pinned to the same step
                    # burn max_failures like any other crash loop.
                    progressed = step is not None and (
                        last_restore_step is None or step > last_restore_step)
                    if step is not None:
                        last_restore_step = step
                    handled = cap >= elastic.min_workers and progressed
                if not handled:
                    failures += 1
                exhausted = max_failures >= 0 and failures > max_failures
                # "fatal" = retrying cannot help (e.g. infeasible resources):
                # return even under max_failures=-1 instead of spinning forever.
                if exhausted or fatal:
                    run_registry.finish_run(run_name, "failed")
                    return Result(
                        metrics=outcome["last_metrics"],
                        checkpoint=(manager.latest_checkpoint()
                                    or self._coordinator_checkpoint(
                                        coordinator, from_memory=False)),
                        path=experiment_path,
                        error=last_error,
                        metrics_history=history,
                        elastic_events=elastic_events,
                    )
                if elastic is not None:
                    time.sleep(0.05)  # resume fast — recovery latency is the product
                else:
                    time.sleep(min(2.0 ** min(failures, 5) * 0.1, 5.0))  # restart backoff
                    # Restart from the latest checkpoint (ref: v2 controller
                    # RESTARTING state).  The coordinator's committed step
                    # wins — its replica tier restores without re-reading
                    # storage; the legacy manager path is the fallback.
                    restore_ckpt = (self._coordinator_checkpoint(coordinator)
                                    or manager.latest_checkpoint()
                                    or self.resume_from_checkpoint)
                    # The restarted attempt re-runs the user loop from its
                    # own epoch 0: ingest epochs must start fresh too.
                    for ingest in ingests.values():
                        ingest.reset()
                attempt_no += 1
                run_registry.update_run(run_name, attempts=attempt_no)
        finally:
            # Device-telemetry rollup for the run row (compile history,
            # pool high-water, transfer tail) — best-effort, the registry
            # write must never mask the real exit path.
            try:
                run_registry.update_run(
                    run_name,
                    device_telemetry=device_telemetry.snapshot())
            except Exception:
                pass
            # A raise out of the attempt loop (controller bug, KeyboardInterrupt)
            # must not leave the registry row "running" forever.
            row = run_registry.get_run(run_name)
            if row is not None and row["status"] == "running":
                run_registry.finish_run(run_name, "failed")
            if coordinator is not None:
                try:
                    ray_tpu.kill(coordinator)
                except Exception:
                    pass

    # ------------------------------------------------ coordinator restore
    def _coordinator_checkpoint(self, coordinator,
                                from_memory: bool = True) -> Optional[Checkpoint]:
        """Checkpoint handle for the coordinator's latest committed step.

        Prefers the in-memory replica tier (full shard set resident):
        payloads are materialized into a fresh local committed dir, so the
        handle's to_pytree() never touches the original storage — the
        Gemini-style fast recovery path.  When the writers' node died WITH
        its object store, the peer ReplicaHolder's copies are next; the
        committed dir on storage is the floor."""
        if coordinator is None:
            return None
        try:
            src = ray_tpu.get(coordinator.restore_source.remote(), timeout=30)
        except Exception:
            return None
        if src is None:
            return None
        if from_memory:
            ckpt = (self._materialize_memory(src)
                    or self._materialize_peer(coordinator, src["step"]))
            if ckpt is not None:
                return ckpt
        return Checkpoint(src["path"])

    def _elastic_restore_point(self, coordinator, manager: CheckpointManager):
        """(checkpoint, step) to resume from after a preemption or grow:
        memory replicas -> peer holder payloads -> committed dir on disk ->
        legacy manager checkpoints (step unknown there).  Every remote
        fetch is bounded, so a dead holder or a lost object-store ref
        falls through to the next tier instead of hanging the recovery."""
        if coordinator is not None:
            try:
                src = ray_tpu.get(coordinator.restore_source.remote(),
                                  timeout=30)
            except Exception:
                src = None
            if src is not None:
                step = src["step"]
                ckpt = (self._materialize_memory(src)
                        or self._materialize_peer(coordinator, step))
                return (ckpt if ckpt is not None
                        else Checkpoint(src["path"])), step
        ckpt = manager.latest_checkpoint()
        return (ckpt, None) if ckpt is not None else (None, None)

    def _materialize_memory(self, src: Dict) -> Optional[Checkpoint]:
        """Local committed dir built from the object-store replica refs;
        None when the set is absent or any ref is unfetchable (its pinning
        node died) within the bound."""
        if not src.get("replicas"):
            return None
        try:
            from ray_tpu.checkpoint import materialize_from_payloads
            from ray_tpu.checkpoint import metrics as _ckpt_metrics

            refs = src["replicas"]["refs"]
            payloads = {int(sid): ray_tpu.get(w["ref"], timeout=20)
                        for sid, w in refs.items()}
            local_root = tempfile.mkdtemp(prefix="ray_tpu_ckpt_mem_")
            path = materialize_from_payloads(local_root, src["step"], payloads)
            _ckpt_metrics.RESTORES.inc(tags={"source": "memory"})
            return Checkpoint(path)
        except Exception:
            return None

    def _materialize_peer(self, coordinator, step: int) -> Optional[Checkpoint]:
        """Same, from the ReplicaHolder actor on a peer node — the tier
        that survives the writers' own node being preempted."""
        try:
            res = ray_tpu.get(coordinator.peer_payloads.remote(step),
                              timeout=30)
        except Exception:
            return None
        if not res:
            return None
        try:
            from ray_tpu.checkpoint import materialize_from_payloads
            from ray_tpu.checkpoint import metrics as _ckpt_metrics

            payloads = {int(sid): p for sid, p in res["payloads"].items()}
            local_root = tempfile.mkdtemp(prefix="ray_tpu_ckpt_peer_")
            path = materialize_from_payloads(local_root, res["step"], payloads)
            _ckpt_metrics.RESTORES.inc(tags={"source": "peer"})
            return Checkpoint(path)
        except Exception:
            return None

    def _dead_workers(self, workers) -> List[int]:
        """Ranks whose worker actor is no longer ALIVE (killed or its node
        preempted)."""
        from ray_tpu._private.runtime import get_runtime

        runtime = get_runtime()
        dead = []
        for rank, w in enumerate(workers):
            try:
                state = runtime.get_actor_state(w._ray_actor_id)
            except Exception:
                continue
            if state is None or state.state == "DEAD":
                # PENDING_CREATION/ALIVE are healthy; RESTARTING resolves
                # through the actor's own restart FSM, not ours.
                dead.append(rank)
        return dead

    def _committed_step(self, coordinator) -> Optional[int]:
        if coordinator is None:
            return None
        try:
            return ray_tpu.get(coordinator.latest_committed.remote(),
                               timeout=10)
        except Exception:
            return None

    def _preempt_worker_node(self, pg) -> Optional[str]:
        """The preempt_node chaos hook: take out a whole node hosting
        worker-group bundles (never the head — the controller lives there)."""
        from ray_tpu._private.runtime import get_runtime
        from ray_tpu.autoscaler.elastic import simulate_preemption

        head = str(get_runtime().head_node_id)
        victim = next((str(n) for n in pg.bundle_node_ids()
                       if n is not None and str(n) != head), None)
        return simulate_preemption(victim)

    # ---------------------------------------------------------- one attempt
    def _run_attempt(self, run_name: str, manager: CheckpointManager,
                     restore_ckpt: Optional[Checkpoint], experiment_path: str,
                     coordinator=None, world: Optional[int] = None,
                     ledgers: Optional[Dict[str, SampleLedger]] = None,
                     ingests: Optional[Dict[str, Any]] = None) -> Dict:
        scfg = self.scaling_config
        if world is None:
            world = scfg.num_workers
        if scfg.elastic is not None:
            # Stable group name + atomic reform: any rank of a preempted
            # attempt still blocked in a rendezvous wakes with an error,
            # and the group's world size tracks the elastic world.
            group_name = f"train-{run_name}"
            collective.reform_collective_group(world, group_name=group_name)
        else:
            DataParallelTrainer._collective_counter += 1
            group_name = f"train-{run_name}-{DataParallelTrainer._collective_counter}"

        # Gang-schedule the worker group via a placement group
        # (ref: backend_executor.py placement group per worker group).
        bundles = [scfg.worker_resources() for _ in range(world)]
        # Infeasible-by-construction requests fail immediately, not after the
        # reservation timeout.
        from ray_tpu._private.runtime import get_runtime
        from ray_tpu._private.scheduling import res_fits

        nodes = get_runtime().scheduler.nodes()
        for bundle in bundles:
            if not any(res_fits(n.total, bundle) for n in nodes if n.alive):
                return {"status": "fatal", "last_metrics": None, "history": [],
                        "error": RuntimeError(
                            f"Worker bundle {bundle} fits no node in the cluster "
                            f"(total: {ray_tpu.cluster_resources()})")}
        pg = placement_group(bundles, strategy=scfg.placement_strategy)
        try:
            if not pg.wait(timeout_seconds=60):
                total = ray_tpu.cluster_resources()
                return {"status": "failed", "last_metrics": None, "history": [],
                        "error": RuntimeError(
                            f"Could not reserve {world}x{scfg.worker_resources()} "
                            f"for the worker group within 60s (cluster: {total}). "
                            f"Reduce num_workers/resources_per_worker or add nodes.")}
            return self._run_with_pg(pg, run_name, group_name, manager,
                                     restore_ckpt, coordinator, world=world,
                                     ledgers=ledgers, ingests=ingests)
        finally:
            collective.destroy_collective_group(group_name)
            remove_placement_group(pg)

    def _worker_mode(self, pg) -> str:
        """threads (one TPU host, shared JAX client) vs processes (one
        jax.distributed rank per worker process — required once the worker
        group spans nodes: a thread here cannot execute on another host)."""
        mode = getattr(self.scaling_config, "worker_mode", "auto")
        if mode in ("threads", "processes"):
            return mode
        if mode != "auto":
            raise ValueError(f"worker_mode must be auto|threads|processes, got {mode!r}")
        from ray_tpu._private.runtime import get_runtime

        head = str(get_runtime().head_node_id)
        return "processes" if any(
            n is not None and n != head for n in pg.bundle_node_ids()
        ) else "threads"

    def _run_with_pg(self, pg, run_name: str, group_name: str,
                     manager: CheckpointManager, restore_ckpt,
                     coordinator=None, world: Optional[int] = None,
                     ledgers: Optional[Dict[str, SampleLedger]] = None,
                     ingests: Optional[Dict[str, Any]] = None) -> Dict:
        if self._worker_mode(pg) == "processes":
            if self.scaling_config.elastic is not None:
                return {"status": "fatal", "last_metrics": None, "history": [],
                        "error": ValueError(
                            "elastic training requires thread-tier workers "
                            "(the sample ledger and replica restore live in "
                            "the controller's process); use ScalingConfig("
                            "worker_mode='threads')")}
            # Process-tier workers ship checkpoints by value through the
            # report queue; the async sharded path is thread-tier only.
            return self._run_distributed(pg, run_name, group_name, manager,
                                         restore_ckpt)
        scfg = self.scaling_config
        elastic = scfg.elastic
        if world is None:
            world = scfg.num_workers
        ledgers = ledgers or {}
        ingests = ingests or {}
        train_metrics.WORLD_SIZE.set(world)
        run_registry.update_run(run_name, world_size=world)
        dataset_shards = self._split_datasets(
            world, exclude=set(ledgers) | set(ingests))
        writers: List = []
        epoch = 0
        start_step = 0
        if coordinator is not None:
            from ray_tpu.checkpoint import ShardWriter

            # New attempt = new epoch: shards from a crashed attempt's
            # in-flight saves can no longer mix into this attempt's steps.
            epoch = ray_tpu.get(coordinator.new_epoch.remote(), timeout=30)
            latest = ray_tpu.get(coordinator.latest_committed.remote(),
                                 timeout=30)
            start_step = (latest + 1) if latest is not None else 0
            writers = [ShardWriter(coordinator, shard_id=rank,
                                   world_size=world, epoch=epoch)
                       for rank in range(world)]
        sessions: List[TrainSession] = []
        workers = []
        for rank in range(world):
            ctx = TrainContext(world_rank=rank, world_size=world, local_rank=rank,
                               trial_name=run_name, experiment_name=run_name,
                               group_name=group_name)
            session = TrainSession(ctx, checkpoint_to_restore=restore_ckpt,
                                   dataset_shards=dataset_shards[rank],
                                   shard_writer=writers[rank] if writers else None,
                                   start_step=start_step,
                                   dataset_config=self.dataset_config,
                                   profiler=(StepProfiler(run_name=run_name,
                                                          rank=rank)
                                             if self.run_config.profile
                                             else None))
            # Elastic datasets are views onto the shared ledger, bound to
            # THIS session so claims carry its next checkpoint step.
            for name, ledger in ledgers.items():
                session.dataset_shards[name] = ElasticDatasetShard(ledger, session)
            # Streaming datasets: a per-session view onto the shared
            # ingest — shard claims carry this session's checkpoint step.
            for name, ingest in ingests.items():
                session.dataset_shards[name] = ingest.make_shard(session)
            sessions.append(session)
            workers.append(
                TrainWorker.options(
                    resources=scfg.worker_resources(),
                    scheduling_strategy=PlacementGroupSchedulingStrategy(
                        placement_group=pg, placement_group_bundle_index=rank),
                ).remote(rank, world, group_name)
            )

        refs = [
            w.run.remote(self.train_loop, self.train_loop_config, s)
            for w, s in zip(workers, sessions)
        ]
        # fit()'s set-up ends as the first worker enters the loop function
        # (a worker that never starts must not hold the controller: 2 s)
        sessions[0].loop_entered.wait(timeout=2.0)
        self._fit_setup.attributes["worker_mode"] = "threads"
        self._fit_setup.end()

        history: List[Dict[str, Any]] = []
        last_metrics: Optional[Dict[str, Any]] = None
        pending = list(refs)
        statuses: List[str] = []
        injector = fault_injection.get_injector()
        grow_target: Optional[int] = None
        desired_max = (elastic.resolve_max(scfg.num_workers)
                       if elastic is not None else world)
        last_seal = 0.0
        last_health = 0.0
        last_grow_check = time.monotonic()
        grow_first_exit: Optional[float] = None
        grow_woke = False
        try:
            while pending:
                ready, pending = ray_tpu.wait(pending, num_returns=len(pending), timeout=0.05)
                last_metrics, new_rows = self._drain_sessions(sessions, manager, last_metrics)
                history.extend(new_rows)
                now = time.monotonic()
                # Liveness: a preempted thread-tier worker's actor dies but
                # its in-flight run() thread does NOT — the ref would never
                # resolve, so the controller polls actor health itself (the
                # same signal serve's health machinery uses).
                if now - last_health >= 0.25:
                    last_health = now
                    dead = self._dead_workers(workers)
                    if dead:
                        from ray_tpu.exceptions import WorkerCrashedError

                        raise WorkerCrashedError(
                            f"{len(dead)} train worker(s) died "
                            f"(ranks {sorted(dead)}; node preempted?)")
                # Seal provisional ledger claims as the coordinator commits
                # their steps: sealed samples never requeue on a rollback.
                if ((ledgers or ingests) and coordinator is not None
                        and now - last_seal >= 0.25):
                    last_seal = now
                    committed = self._committed_step(coordinator)
                    if committed is not None:
                        run_registry.update_run(
                            run_name, last_committed_step=committed)
                        for ledger in ledgers.values():
                            ledger.seal(committed)
                        for ingest in ingests.values():
                            ingest.seal(committed)
                # Chaos: a whole worker node vanishes (TPU slice preempted).
                if injector.enabled and injector.fires("preempt_node"):
                    self._preempt_worker_node(pg)
                # Grow back toward the target world at a checkpoint boundary
                # once capacity returns (and there is a step to restore —
                # growing without one would mean training from scratch).
                if (elastic is not None and grow_target is None
                        and world < desired_max
                        and now - last_grow_check >= elastic.grow_check_period_s):
                    last_grow_check = now
                    from ray_tpu.autoscaler.elastic import worker_capacity

                    target = min(worker_capacity(scfg.worker_resources()),
                                 desired_max)
                    has_restore = (self._committed_step(coordinator) is not None
                                   or manager.latest_checkpoint() is not None)
                    if target > world and has_restore:
                        grow_target = target
                        # report() IS the checkpoint boundary: each worker
                        # raises StopIteration there and returns "stopped".
                        for s in sessions:
                            s.stop_requested.set()
                for r in ready:
                    try:
                        statuses.append(ray_tpu.get(r))  # raise worker errors
                    except (TaskError, RayTpuError):
                        if grow_target is None:
                            raise
                        # Interrupted mid-rendezvous by the boundary wake
                        # below: its uncommitted claims roll back with the
                        # grow restore, so this is a clean stop.
                        statuses.append("stopped")
                # Grow-stop liveness: workers observe the stop at different
                # lockstep points — one can exit at its report() while a
                # peer already entered the next collective and now waits on
                # a partner that will never arrive.  Once anyone has exited,
                # give the rest a grace window to reach their own boundary,
                # then wake them by destroying the group (their collective
                # raises; swallowed as "stopped" above).
                if grow_target is not None and statuses and pending:
                    if grow_first_exit is None:
                        grow_first_exit = now
                    elif not grow_woke and now - grow_first_exit >= 1.0:
                        grow_woke = True
                        try:
                            collective.get_collective_group(
                                group_name).destroy()
                        except ValueError:
                            pass
            # Final drain after workers exit.
            last_metrics, new_rows = self._drain_sessions(sessions, manager, last_metrics)
            history.extend(new_rows)
            # Async saves still persisting in the background belong to this
            # run: let them land (and commit) before declaring it finished —
            # and, on a grow, before the restore point is chosen.
            for wtr in writers:
                try:
                    wtr.drain(timeout=120)
                except Exception:
                    pass
                wtr.close()
            if (ledgers or ingests) and coordinator is not None:
                committed = self._committed_step(coordinator)
                if committed is not None:
                    run_registry.update_run(
                        run_name, last_committed_step=committed)
                    for ledger in ledgers.values():
                        ledger.seal(committed)
                    for ingest in ingests.values():
                        ingest.seal(committed)
            # A grow stop can surface two ways: workers that hit report()
            # raise StopIteration ("stopped"), but workers whose user loop
            # exits because the ledger fence returned None come back
            # "finished" — the ledger still holding work distinguishes that
            # from a genuine end-of-dataset finish.
            work_left = any(not led.exhausted() for led in ledgers.values()) \
                or any(not ing.exhausted() for ing in ingests.values())
            if grow_target is not None and ("stopped" in statuses or work_left):
                return {"status": "grow", "new_world": grow_target,
                        "last_metrics": last_metrics, "history": history,
                        "error": None}
            return {"status": "finished", "last_metrics": last_metrics,
                    "history": history,
                    "error": self._check_async_saves(sessions, coordinator)}
        except (TaskError, RayTpuError) as e:  # worker failed
            failed_at = time.monotonic()
            for s in sessions:
                s.stop_requested.set()
            # Wake any worker blocked in a collective rendezvous NOW (the
            # group destroy in the caller's finally would also do it, but
            # draining results first needs them unwedged).
            try:
                collective.get_collective_group(group_name).destroy()
            except ValueError:
                pass
            for w in workers:
                ray_tpu.kill(w)
            # Queued-but-unstarted async saves die with the attempt (their
            # epoch is stale anyway); an in-flight persist may still commit,
            # which is always safe — the step is fully written.
            for wtr in writers:
                wtr.close()
            # Keep results reported before the crash (checkpoints especially —
            # the restart resumes from the last one registered).
            last_metrics, new_rows = self._drain_sessions(sessions, manager, last_metrics)
            history.extend(new_rows)
            # Highest step any session reported (its save may or may not
            # have committed) — the elastic controller's lost-step count is
            # this minus the restore step.
            last_step = max((s._ckpt_step - 1 for s in sessions), default=-1)
            return {"status": "failed", "last_metrics": last_metrics,
                    "history": history, "error": e,
                    "failed_at": failed_at,
                    "last_step": last_step if last_step >= 0 else None}

    # ------------------------------------------------- multi-host attempt
    def _run_distributed(self, pg, run_name: str, group_name: str,
                         manager: CheckpointManager, restore_ckpt) -> Dict:
        """One attempt with process-tier workers spanning worker nodes.

        rank 0 reserves the jax.distributed coordinator on its own host,
        every worker joins with its placement-group rank, and the group's
        collectives become global SPMD programs (ref: backend_executor.py
        _setup_worker_group + torch/config.py:115 — the same
        coordinator-address + rank/world bootstrap, NCCL swapped for XLA)."""
        from ray_tpu.train.checkpoint import pack_checkpoint, unpack_checkpoint
        from ray_tpu.util.queue import Empty, Queue

        scfg = self.scaling_config
        world = scfg.num_workers
        if self.datasets:
            return {"status": "fatal", "last_metrics": None, "history": [],
                    "error": ValueError(
                        "datasets= require thread-tier workers (streaming "
                        "iterators cannot cross process boundaries); use "
                        "ScalingConfig(worker_mode='threads') or load data "
                        "inside the train_loop")}
        node_ids = pg.bundle_node_ids()
        node_order: List[Optional[str]] = []
        for n in node_ids:
            if n not in node_order:
                node_order.append(n)
        local_counter: Dict[Optional[str], int] = {}
        workers = []
        contexts: List[TrainContext] = []
        for rank in range(world):
            n = node_ids[rank] if rank < len(node_ids) else None
            local_rank = local_counter.get(n, 0)
            local_counter[n] = local_rank + 1
            contexts.append(TrainContext(
                world_rank=rank, world_size=world, local_rank=local_rank,
                node_rank=node_order.index(n), trial_name=run_name,
                experiment_name=run_name, group_name=group_name))
            workers.append(
                JaxDistTrainWorker.options(
                    isolation="process",
                    resources=scfg.worker_resources(),
                    scheduling_strategy=PlacementGroupSchedulingStrategy(
                        placement_group=pg, placement_group_bundle_index=rank),
                ).remote(rank, world, group_name))

        report_queue = Queue()
        history: List[Dict[str, Any]] = []
        last_metrics: Optional[Dict[str, Any]] = None

        def drain() -> None:
            nonlocal last_metrics
            while True:
                try:
                    item = report_queue.get_nowait()
                except Empty:
                    return
                if item.get("checkpoint_blob"):
                    # unpack lands in a ray_tpu_ckpt_ tempdir, which
                    # register() MOVES into managed storage (no double copy).
                    manager.register(unpack_checkpoint(item["checkpoint_blob"]),
                                     item["metrics"])
                if item["rank"] == 0:
                    last_metrics = item["metrics"]
                    history.append(item["metrics"])

        try:
            coord = ray_tpu.get(workers[0].reserve_coordinator.remote(),
                                timeout=120)
            ray_tpu.get([w.setup.remote(coord) for w in workers], timeout=300)
            blob = pack_checkpoint(restore_ckpt)
            refs = [w.run.remote(self.train_loop, self.train_loop_config, ctx,
                                 report_queue, blob)
                    for w, ctx in zip(workers, contexts)]
            self._fit_setup.attributes["worker_mode"] = "processes"
            self._fit_setup.end()
            _drive_worker_refs(refs, drain)
            for w in workers:
                try:
                    ray_tpu.get(w.teardown.remote(), timeout=15)
                except Exception:
                    pass
            return {"status": "finished", "last_metrics": last_metrics,
                    "history": history, "error": None}
        except (TaskError, RayTpuError) as e:
            # A dead node/worker leaves the others wedged inside a global
            # SPMD collective; killing their processes (finally below) is
            # what unblocks the restart.
            drain()
            return {"status": "failed", "last_metrics": last_metrics,
                    "history": history, "error": e}
        finally:
            for w in workers:
                try:
                    ray_tpu.kill(w)
                except Exception:
                    pass
            try:
                report_queue.shutdown()
            except Exception:
                pass

    def _check_async_saves(self, sessions: List[TrainSession],
                           coordinator) -> Optional[BaseException]:
        """Async saves fail out-of-band (drain deliberately swallows them so
        a later commit can supersede); a run where NO save ever committed
        must not finish silently with checkpoint=None and no error."""
        reported = sum(getattr(s, "async_saves_reported", 0) for s in sessions)
        if not reported or coordinator is None:
            return None
        from ray_tpu.checkpoint.writer import _invoke

        try:
            latest = _invoke(coordinator, "latest_committed")
        except Exception:
            return None
        if latest is not None:
            return None
        causes = []
        for s in sessions:
            handle = getattr(s, "last_save_handle", None)
            if handle is None:
                continue
            try:
                exc = handle.exception(timeout=0)
            except Exception:
                exc = None
            if exc is not None:
                causes.append(repr(exc))
        import logging

        err = RuntimeError(
            f"{reported} async checkpoint save(s) were reported but no step "
            "ever committed — the run finished without a usable checkpoint"
            + (f"; last shard errors: {causes}" if causes else ""))
        logging.getLogger(__name__).warning("%s", err)
        return err

    def _drain_sessions(self, sessions: List[TrainSession], manager: CheckpointManager,
                        last_metrics: Optional[Dict[str, Any]]):
        history = []
        drained = False
        # The controller's thread shares the GIL with thread-tier workers'
        # step loops: each pass is on the profiler's timeline.
        with tracing.annotate("train.result_drain"):
            for session in sessions:
                while True:
                    try:
                        item = session.results.get_nowait()
                    except queue.Empty:
                        break
                    drained = True
                    # Metrics history follows rank 0 (the reference's
                    # convention), but checkpoints from ANY rank are
                    # registered — a loop where a non-zero rank carries the
                    # checkpoint must not lose progress.
                    if item["checkpoint"] is not None:
                        manager.register(item["checkpoint"], item["metrics"])
                    if item["rank"] == 0:
                        last_metrics = item["metrics"]
                        history.append(item["metrics"])
        # First report after an elastic recovery = training resumed: close
        # the kill->resumed clock.
        if drained and self._recovery_t0 is not None:
            dt = time.monotonic() - self._recovery_t0
            train_metrics.RECOVERY_SECONDS.observe(dt)
            ev = self._recovery_event or {}
            # Timeline lane: the whole failure->resumed window as one span,
            # so a trace shows shrink/grow gaps between train.step rows.
            now_w = time.time()
            tracing.record_span("train.elastic", now_w - dt, now_w,
                                attributes={"type": ev.get("type", ""),
                                            "from_world": ev.get("from_world"),
                                            "to_world": ev.get("to_world"),
                                            "restore_step": ev.get("restore_step")})
            if self._recovery_event is not None:
                self._recovery_event["recovery_seconds"] = dt
            self._recovery_t0 = None
            self._recovery_event = None
        return last_metrics, history

    def _split_datasets(self, world: int, exclude=()) -> List[Dict[str, Any]]:
        """Per-rank dataset shards (ref: StreamSplitDataIterator coordinated
        split for Train ingest, data/_internal/iterator/stream_split_iterator.py:31).
        Names in ``exclude`` are served by the elastic sample ledger instead."""
        shards: List[Dict[str, Any]] = [{} for _ in range(world)]
        for name, ds in self.datasets.items():
            if name in exclude:
                continue
            if hasattr(ds, "streaming_split"):
                its = ds.streaming_split(world)
                for rank in range(world):
                    shards[rank][name] = its[rank]
            else:
                for rank in range(world):
                    shards[rank][name] = ds
        return shards


class JaxTrainer(DataParallelTrainer):
    """The TPU trainer (BASELINE north star: `JaxTrainer` pinning workers to
    TPU processes).  Identical controller; workers join the 'xla' collective
    group so `ray_tpu.collective.allreduce` inside the loop compiles to psum
    over ICI, and `use_tpu=True` reserves chips per worker.

    Single host, the workers are threads sharing one JAX client (mesh mode).
    When the placement group lands workers on OTHER nodes (or
    ``ScalingConfig(worker_mode="processes")``), each worker becomes its own
    OS process joined into one jax.distributed cluster: jax.devices() spans
    every worker's chips, meshes ride ICI within a host and DCN across, and
    the same train_loop runs unchanged (multi-controller SPMD).  That tier
    has only run on CPU devices: several processes dividing one host's chips
    need per-process chip visibility that nothing here sets."""

    def fit(self) -> Result:
        # Thread-tier workers compile in this process; process-tier ranks
        # configure their own in JaxDistTrainWorker.setup.
        from ray_tpu.parallel.compile_cache import configure_compile_cache

        configure_compile_cache()
        return super().fit()
