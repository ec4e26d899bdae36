"""Host time inside one call of the jitted train step (``TrainStep.__call__``,
span ``train.dispatch``: the enqueue, not the device's work), from the
program's own ``StepProfiler`` rows (counter ``dispatch``); mean over the
window's steps.  ``describe`` adds the median and the longest single call, so
that a hiccup that moved the mean can be named."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "trainer", "ms/step", "program_span", \
    "tokens_per_s_per_chip"


def read(run):
    rows = run.profiler_rows
    return run.bucket_ms("dispatch") if rows and "dispatch" in rows[0] else None


def describe(run):
    return anatomy.span_note(run, "dispatch")
