"""Host time inside one ``train.report`` call (span ``train.report``, the step
boundary's bookkeeping included; the loss stays a device array), from the
program's own ``StepProfiler`` rows (counter ``report``); mean over the
window's steps.  ``describe`` adds the median and the longest single call, so
that a hiccup that moved the mean can be named."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "trainer", "ms/step", "program_span", \
    "tokens_per_s_per_chip"


def read(run):
    rows = run.profiler_rows
    return run.bucket_ms("report") if rows and "report" in rows[0] else None


def describe(run):
    return anatomy.span_note(run, "report")
