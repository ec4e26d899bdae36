"""Host time ``TrainStep.__call__`` spends, after the jitted call has
returned, giving the worker's step profiler the step's sentinel and reading
its counter refs (``TrainStep._hand_over``: what the device facts of a row
cost the worker's thread), from the program's own ``StepProfiler`` rows
(counter ``hand_over``, beside ``dispatch``); mean over the window's steps.
``describe`` adds the median and the longest single call."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "trainer", "ms/step", "program_span", \
    "tokens_per_s_per_chip"


def read(run):
    rows = run.profiler_rows
    return run.bucket_ms("hand_over") if rows and "hand_over" in rows[0] \
        else None


def describe(run):
    return anatomy.span_note(run, "hand_over")
