"""Per step, the self time of the first chip's instructions in the trace's
steady stretch that the program's ``TrainStep.anatomy()`` puts in the parts
of an expert layer that holds a share of the experts, all phases summed:
``router`` (logits over every published expert, softmax, top-k, the
load-balance loss), ``moe_dispatch`` (the sort of all positions x experts a
token pairs, the gathers into expert order and back, the weighted sum) and
``moe_held`` (the grouped matmuls over the held groups and the activation
between them).  ``describe`` keeps the three apart, by phase.  None where the
program has no such scope (``anatomy`` finds nothing of the three)."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PARTS = ("router", "moe_dispatch", "moe_held")


def read(run):
    table = anatomy.table(run)
    if not table or not any(key.endswith("/moe_held") for key in table):
        return None
    return anatomy.part_ms(run, *PARTS)


def describe(run):
    table = anatomy.table(run)
    return table and {key: ms for key, ms in table.items()
                      if key.split("/")[1] in PARTS}
