"""Per step, the self time of the first chip's instructions in the trace's
steady stretch that the program's ``TrainStep.anatomy()`` puts in the routed
part of an expert layer that has a shared expert beside it, all phases
summed: ``router`` (the scores over every published expert, the selection
bias, top-k), ``moe_dispatch`` (the sort of all (token, expert) pairs, the
windows' gathers into expert order and back, the sums) and ``moe_held`` (the
grouped matmuls over the held groups and the activation between them);
``step.moe_shared_ms`` is the shared expert.  ``describe`` keeps the three
apart, by phase.  None where the program has no ``shared_expert`` scope
(``step.moe_held_ms`` reads the same three parts where it has none)."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PARTS = ("router", "moe_dispatch", "moe_held")


def read(run):
    table = anatomy.table(run)
    if not table or not any(key.endswith("/shared_expert") for key in table):
        return None
    return anatomy.part_ms(run, *PARTS)


def describe(run):
    table = anatomy.table(run)
    return table and {key: ms for key, ms in table.items()
                      if key.split("/")[1] in PARTS}
