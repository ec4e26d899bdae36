"""The rows an expert layer that holds a share moved into expert order and
back, a layer a step: the step counter ``moe_moved`` of the ``StepProfiler``
rows (``models/moe.py``: a window's rows for each window of the held run that
the step's own count of held rows made the layer walk; every pair the layer
sorts when the run is that long), [layer][batch shard]; mean over the
window's rows, the layers and the shards.  It is what the layer's gathers,
activation pass and combine follow, as the kernels follow
``step.moe_held_rows``, and it is never under that count.  ``describe``: the
share of (layer, step)s by the rows they moved, the share that moved every
pair, and the mean per layer.  None where the rows lack the counter: a program
before PR 38, and a model that holds every expert, which moves every pair at
once and counts nothing."""
import numpy as np

from benchmarks.lib import device_rows

LAYER, UNIT, SOURCE, MOVES = "step", "count", "program_counter", \
    "tokens_per_s_per_chip"


def _moved(run):
    """``moe_moved`` as (window rows, layers, batch shards)."""
    rows = run.profiler_rows
    if not rows or any("moe_moved" not in r for r in rows):
        return None
    return np.asarray([r["moe_moved"] for r in rows], dtype=np.int64)


def read(run):
    moved = _moved(run)
    return None if moved is None else float(moved.mean())


def describe(run):
    moved = _moved(run)
    if moved is None:
        return None
    # a shard sorts its own share of the chip's pairs
    pairs = device_rows.pairs_per_layer(run) // moved.shape[2]
    amounts, counts = np.unique(moved, return_counts=True)
    return {"rows": moved.shape[0], "pairs_a_layer": pairs,
            "share_of_layer_steps_by_rows_moved": {
                str(int(amount)): float(count / moved.size)
                for amount, count in zip(amounts, counts)},
            "moved_every_pair": float((moved >= pairs).mean()),
            "per_layer_mean": moved.mean(axis=(0, 2)).tolist()}
