"""Per step, the self time of the first chip's instructions in the trace's
steady stretch that have neither a phase nor a part in the program's
``TrainStep.anatomy()`` (or are not in it at all): the guard against the
names rotting after a refactor, and against a compile-cache entry written by
a tree without them.  ``describe`` keeps the whole breakdown by phase and part
and the nameless instructions that took the most time, for the run's
report."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"


def read(run):
    return anatomy.ms_per_step(run, lambda key: key == (None, None))


def describe(run):
    table = anatomy.table(run)
    return table and {"ms_by_phase_and_part": table,
                      "longest_nameless": anatomy.longest(
                          run, lambda key: key == (None, None))}
