"""Per step, the self time of the first chip's instructions in the trace's
steady stretch that the program's ``TrainStep.anatomy()`` puts in the
hyper-connections of a residual of several streams
(``models/streams.py``), all phases summed: the part ``mhc`` and the two
nested in it, ``mhc_maps`` (the norm over every stream, the product with
``phi``, the sigmoids, the Sinkhorn turns) and ``mhc_mix`` (the read and the
write, the model's residual add), over every sub-layer, a prediction
module's among them.  The sub-layers' branches run under their kinds' own
scopes and are not in it.  ``describe`` keeps the three apart, by phase.
None where the program has no such scope."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PARTS = ("mhc", "mhc_maps", "mhc_mix")


def read(run):
    table = anatomy.table(run)
    if not table or not any(key.split("/")[1] in PARTS for key in table):
        return None
    return anatomy.part_ms(run, *PARTS)


def describe(run):
    table = anatomy.table(run)
    return table and {key: ms for key, ms in table.items()
                      if key.split("/")[1] in PARTS}
