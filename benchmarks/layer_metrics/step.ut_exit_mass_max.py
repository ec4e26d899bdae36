"""Whether a looped stack's exit gate (``models/looped.py``) has collapsed
onto one pass: the largest entry of the step counter ``ut_exit_mass``
(``StepProfiler`` rows; float32 (ut_steps,): the mean over a step's positions
of the probability of leaving at each pass, summing to one) over the window's
rows and the passes.  1.0 means every position leaves at one pass and the
other passes' heads train on nothing; the gate starts at (1/2, 1/4, ..).
``describe``: every pass's mass on the window's first and last row and its
largest over the window.  None where the program leaves no such counter."""
import numpy as np

LAYER, UNIT, SOURCE, MOVES = "step", "1", "program_counter", \
    "tokens_per_s_per_chip"
COUNTER = "ut_exit_mass"


def _rows(run):
    rows = [r[COUNTER] for r in run.profiler_rows if COUNTER in r]
    return np.asarray(rows, dtype=np.float64) if rows else None


def read(run):
    rows = _rows(run)
    return None if rows is None else float(rows.max())


def describe(run):
    rows = _rows(run)
    return None if rows is None else {
        "rows": rows.shape[0], "per_pass_max": rows.max(axis=0).tolist(),
        "first_row": rows[0].tolist(), "last_row": rows[-1].tolist()}
