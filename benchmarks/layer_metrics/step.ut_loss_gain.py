"""What the later passes of a looped stack (``models/looped.py``) do that the
first does not: the first pass's mean cross-entropy less the last's,
``loss_ut[0] - loss_ut[T - 1]``, on the window's last row of the step counter
``loss_ut`` (``StepProfiler`` rows; float32 (ut_steps,)), in nats.  Near 0
the passes after the first add nothing the first head does not already say.
``describe``: every pass's on the window's first and last row.  None where
the program leaves no such counter."""
import numpy as np

LAYER, UNIT, SOURCE, MOVES = "step", "nats", "program_counter", \
    "tokens_per_s_per_chip"
COUNTER = "loss_ut"


def _rows(run):
    rows = [r[COUNTER] for r in run.profiler_rows if COUNTER in r]
    return np.asarray(rows, dtype=np.float64) if rows else None


def read(run):
    rows = _rows(run)
    return None if rows is None else float(rows[-1, 0] - rows[-1, -1])


def describe(run):
    rows = _rows(run)
    return None if rows is None else {
        "rows": rows.shape[0], "first_row": rows[0].tolist(),
        "last_row": rows[-1].tolist()}
