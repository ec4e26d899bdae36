"""Seconds of the train step's first call spent tracing (jax's
``jaxpr_trace_duration`` time spans on the calling thread, nested jits
counted once), from the ``trace_s`` of the first-call record the program's
``TrainStep`` left (span ``train.first_call``).  ``describe`` gives the four
phases, the rest and the cache's answer for all three labelled programs of
the set-up, so that one traced run's report holds the whole split."""
from benchmarks.lib import setup_registry

LAYER, UNIT, SOURCE, MOVES = "step", "s", "program_span", "setup_s"


def read(run):
    return setup_registry.first_call().get("trace_s")


def describe(run):
    return {label: setup_registry.phases(label)
            for label in ("init_params", "init_opt_state", "train_step")}
