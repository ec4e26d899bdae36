"""Per step, the self time of the first chip's instructions in the trace's
steady stretch that the program's ``TrainStep.anatomy()`` puts in the part
``noise`` (block-diffusion training: the draw of the masked positions from
the row and the seed, the noised copy, its concatenation with the clean one,
the loss weights).  None where the program has no such scope."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"


def read(run):
    table = anatomy.table(run)
    if not table or not any(key.endswith("/noise") for key in table):
        return None
    return anatomy.part_ms(run, "noise")
