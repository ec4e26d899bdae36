"""The largest distance from 1 of a row or column sum of a sub-layer's
normalised stream map (``models/streams.py``): the step counter
``mhc_sinkhorn_err`` of the ``StepProfiler`` rows, float32 (sub-layers,), a
prediction module's last; the largest over the window's rows and the
sub-layers.  It says whether ``hc_sinkhorn_iters`` turns reach the manifold
at the logits training drives the maps to.  ``describe``: per sub-layer the
largest over the window, and the window's first and last row.  None where
the program leaves no such counter."""
import numpy as np

LAYER, UNIT, SOURCE, MOVES = "step", "1", "program_counter", \
    "tokens_per_s_per_chip"
COUNTER = "mhc_sinkhorn_err"


def _rows(run):
    rows = [r[COUNTER] for r in run.profiler_rows if COUNTER in r]
    return np.asarray(rows, dtype=np.float64) if rows else None


def read(run):
    rows = _rows(run)
    return None if rows is None else float(rows.max())


def describe(run):
    rows = _rows(run)
    return None if rows is None else {
        "rows": rows.shape[0], "per_sublayer_max": rows.max(axis=0).tolist(),
        "first_row": rows[0].tolist(), "last_row": rows[-1].tolist()}
