"""Per step, the self time of the first chip's instructions that the
program's ``TrainStep.anatomy()`` puts in the parts ``router`` and
``moe_dispatch``, all phases summed: what the expert layer costs besides its
matmuls (routing, the sort, the gathers into expert order and back, the
weighted combine).  ``describe`` names the instructions that took the most."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PARTS = ("router", "moe_dispatch")


def read(run):
    return anatomy.part_ms(run, *PARTS)


def describe(run):
    return {"longest": anatomy.longest(run, lambda key: key[1] in PARTS)}
