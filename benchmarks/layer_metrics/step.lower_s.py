"""Seconds of the train step's first call spent lowering the traced step to
MLIR (jax's ``jaxpr_to_mlir_module_duration`` time spans), from the
``lower_s`` of the first-call record (span ``train.first_call``)."""
from benchmarks.lib import setup_registry

LAYER, UNIT, SOURCE, MOVES = "step", "s", "program_span", "setup_s"


def read(run):
    return setup_registry.first_call().get("lower_s")
