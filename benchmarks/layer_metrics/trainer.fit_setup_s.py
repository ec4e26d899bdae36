"""Seconds from ``JaxTrainer.fit()``'s entry to the moment the worker enters
the loop function: placement group, workers, dataset split, sessions (span
``train.fit_setup``, a row of the program's
``device_telemetry.setup_account()``)."""
from benchmarks.lib import setup_registry

LAYER, UNIT, SOURCE, MOVES = "trainer", "s", "program_span", "setup_s"


def read(run):
    return setup_registry.row_seconds("train.fit_setup")
