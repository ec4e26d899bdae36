"""Per step, the self time of the first chip's instructions in the trace's
steady stretch that the program's ``TrainStep.anatomy()`` puts in a
gated-delta-net layer (``models/gdn.py``), all phases summed: the part
``gdn`` (projections, L2 norms, the decay, beta, the head norm, the output
gate, out-projection, the norm after it) and the two nested in it,
``gdn_conv`` and ``gdn_scan``, as ``step.kda_ms`` holds its three.
``describe`` keeps the three apart, by phase.  None where the program has no
such scope."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PARTS = ("gdn", "gdn_conv", "gdn_scan")


def read(run):
    table = anatomy.table(run)
    if not table or not any(key.split("/")[1] in PARTS for key in table):
        return None
    return anatomy.part_ms(run, *PARTS)


def describe(run):
    table = anatomy.table(run)
    return table and {key: ms for key, ms in table.items()
                      if key.split("/")[1] in PARTS}
