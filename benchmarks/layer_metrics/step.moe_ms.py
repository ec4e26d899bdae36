"""Per step, the self time of the first chip's instructions in the trace's
steady stretch that the program's ``TrainStep.anatomy()`` puts in the expert
layer's parts, all phases summed: ``router`` (logits, softmax, top-k, the two
losses), ``moe_dispatch`` (sort, counts, the gathers into expert order and
back, the weighted sum) and ``experts`` (the grouped matmuls and the
activation between them).  ``describe`` keeps the three apart, by phase."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PARTS = ("router", "moe_dispatch", "experts")


def read(run):
    return anatomy.part_ms(run, *PARTS)


def describe(run):
    table = anatomy.table(run)
    return table and {key: ms for key, ms in table.items()
                      if key.split("/")[1] in PARTS}
