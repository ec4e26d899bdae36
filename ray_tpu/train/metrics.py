"""Elastic-training metrics.

Declared at import time like the serve/checkpoint metric modules so
``scripts/check_metrics.py`` lints them; exported on ``/metrics`` through
the process registry (util/metrics.py).

The anchor set is what an operator of preemption-tolerant training needs
on a dashboard: how often slices vanish, how the trainer responded
(shrink/grow), how much work each recovery cost (lost steps — bounded by
``CheckpointConfig.replica_memory_steps`` when the memory tier is on),
and how long kill→training-resumed took.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ray_tpu.util.metrics import Counter, Gauge, Histogram

PREEMPTIONS = Counter(
    "ray_tpu_elastic_preemptions_total",
    "Worker/node preemptions observed by the elastic training layer "
    "(simulated ones from the preempt_node chaos hook included)",
)

SHRINK_EVENTS = Counter(
    "ray_tpu_elastic_shrink_events_total",
    "Times the elastic trainer shrank its world size to surviving "
    "capacity after a worker or node loss",
)

GROW_EVENTS = Counter(
    "ray_tpu_elastic_grow_events_total",
    "Times the elastic trainer grew its world size back at a checkpoint "
    "boundary after capacity returned",
)

LOST_STEPS = Counter(
    "ray_tpu_elastic_lost_steps_total",
    "Training steps rolled back across all elastic recoveries (steps "
    "reported after the last committed checkpoint at failure time)",
)

RECOVERY_SECONDS = Histogram(
    "ray_tpu_elastic_recovery_seconds",
    "Seconds from failure detection to the first report() of the resumed "
    "attempt (restore + group reform + data reshard)",
)

WORLD_SIZE = Gauge(
    "ray_tpu_elastic_world_size",
    "Current world size of the elastic training worker group",
)

# ---------------------------------------------------------------- profiler
# Live step-time attribution from ray_tpu.train.profiler: every step's
# wall clock split into data-wait / h2d / compute / collective / ckpt-block
# buckets, plus the derived MFU / tokens-per-second / starvation gauges
# the multi-chip MFU push and the metrics-driven autoscaler consume.

STEPS_PROFILED = Counter(
    "ray_tpu_train_steps_total",
    "Training steps closed by the step profiler (report() boundaries)",
)

STEP_SECONDS = Histogram(
    "ray_tpu_train_step_seconds",
    "Seconds per training step: on the device (the time between two "
    "steps' ends, the row's device_period) where the step went through "
    "TrainStep, else report() to report()",
    boundaries=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                1.0, 2.5, 5.0, 10.0, 30.0, 60.0),
)

STEP_P50_SECONDS = Gauge(
    "ray_tpu_train_step_p50_seconds",
    "Median step time (see ray_tpu_train_step_seconds) over the profiler's "
    "recent-step window",
)

STEP_P95_SECONDS = Gauge(
    "ray_tpu_train_step_p95_seconds",
    "95th-percentile step time (see ray_tpu_train_step_seconds) over the "
    "profiler's recent-step window",
)

STEP_BUCKET_SECONDS = Gauge(
    "ray_tpu_train_step_bucket_seconds",
    "Last step's wall-time attribution per bucket (data_wait / h2d / "
    "compute / collective / ckpt_block); buckets sum to the step wall",
    ("bucket",),
)

DATA_STARVED_FRACTION = Gauge(
    "ray_tpu_train_data_starved_fraction",
    "Fraction of the last step's wall time spent blocked on the input "
    "pipeline (the per-step view of ingest starved-seconds)",
)

TOKENS_PER_SECOND = Gauge(
    "ray_tpu_train_tokens_per_second",
    "Training throughput from the step profiler: tokens_per_step over the "
    "last step's time on the device (requires "
    "profiler.configure(tokens_per_step=...))",
)

MFU = Gauge(
    "ray_tpu_train_mfu",
    "Model FLOPs utilization of the last step: flops_per_step / the step's "
    "time on the device / peak_flops (requires "
    "profiler.configure(flops_per_step=..., peak_flops=...))",
)

STEPS_IN_FLIGHT = Gauge(
    "ray_tpu_train_steps_in_flight",
    "Steps the device had not finished when the last resolved step was "
    "dispatched; 0 means the chip had nothing to do and the host is what "
    "it waits for",
)

MOE_LOAD_MAX = Gauge(
    "ray_tpu_train_moe_load_max",
    "Expert layers of the last resolved step: the largest group of rows "
    "routed to an expert held here over the mean group, mean over layers "
    "and batch shards (1.0 is an even router)",
)


def moe_load_max(moe_rows) -> Optional[float]:
    """From a row's ``moe_rows`` (layers x batch shards x held experts):
    the largest held group over the mean held group of each layer and
    shard that sent a held expert any row, the shards averaged, then the
    layers; None if no layer sent any."""
    rows = np.asarray(moe_rows, dtype=np.float64)
    mean = rows.mean(axis=-1)
    with np.errstate(invalid="ignore", divide="ignore"):
        load = np.where(mean > 0, rows.max(axis=-1) / mean, np.nan)
    layers = [np.nanmean(shards) for shards in load.reshape(-1, load.shape[-1])
              if not np.isnan(shards).all()]
    return float(np.mean(layers)) if layers else None


MOE_MOVED_ROWS = Gauge(
    "ray_tpu_train_moe_moved_rows",
    "Expert layers that hold a share of the experts, last resolved step: "
    "the rows a layer moved into expert order and back, mean over layers "
    "and batch shards (a whole number of models/moe.py's windows: as many "
    "as the step's held rows needed)",
)


def moe_moved_rows(moe_moved) -> float:
    """From a row's ``moe_moved`` (layers x batch shards): the mean."""
    return float(np.mean(moe_moved))


#: What a gauge shows of a step counter (``tracing.STEP_COUNTER_REGISTRY``)
#: once a row has it: counter -> (gauge, what it derives from the value).
COUNTER_GAUGES = {"moe_rows": (MOE_LOAD_MAX, moe_load_max),
                  "moe_moved": (MOE_MOVED_ROWS, moe_moved_rows)}
