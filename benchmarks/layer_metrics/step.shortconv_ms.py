"""Per step, the self time of the first chip's instructions in the trace's
steady stretch that the program's ``TrainStep.anatomy()`` puts in a gated
short-convolution layer (``models/shortconv.py``), all phases summed: the
part ``shortconv`` (norm, in-projection, out-projection) and the one nested
in it, ``shortconv_gate``, as ``step.ssm_ms`` holds ``ssm_conv``.
``describe`` keeps the two apart, by phase.  None where the program has no
such scope."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PARTS = ("shortconv", "shortconv_gate")


def read(run):
    table = anatomy.table(run)
    if not table or not any(key.split("/")[1] in PARTS for key in table):
        return None
    return anatomy.part_ms(run, *PARTS)


def describe(run):
    table = anatomy.table(run)
    return table and {key: ms for key, ms in table.items()
                      if key.split("/")[1] in PARTS}
