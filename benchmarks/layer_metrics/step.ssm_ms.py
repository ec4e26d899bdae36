"""Per step, the self time of the first chip's instructions in the trace's
steady stretch that the program's ``TrainStep.anatomy()`` puts in a Mamba-2
layer (``models/mamba2.py``), all phases summed: the part ``ssm`` (norm,
in-projection, gate, grouped norm, out-projection) and the two nested in it,
``ssm_conv`` and ``ssm_scan``, as ``step.attn_ms`` holds ``attn_kernel``.
``describe`` keeps the three apart, by phase.  None where the program has no
such scope."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PARTS = ("ssm", "ssm_conv", "ssm_scan")


def read(run):
    table = anatomy.table(run)
    if not table or not any(key.split("/")[1] in PARTS for key in table):
        return None
    return anatomy.part_ms(run, *PARTS)


def describe(run):
    table = anatomy.table(run)
    return table and {key: ms for key, ms in table.items()
                      if key.split("/")[1] in PARTS}
