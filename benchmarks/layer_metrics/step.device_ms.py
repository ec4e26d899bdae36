"""Median period between starts of the step program on the first chip, from
the trace's ``XLA Modules`` line."""
LAYER, UNIT, SOURCE, MOVES = "step", "ms", "device_trace", \
    "tokens_per_s_per_chip"


def read(run):
    return 1e3 * run.step_seconds if run.step_seconds else None
