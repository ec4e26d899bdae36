"""Per step, the self time of the first chip's instructions in the trace's
steady stretch that the program's ``TrainStep.anatomy()`` puts in the model
part ``lm_head`` (final norm, logits, loss), all phases summed (forward, backward and any recomputation).
``lib/anatomy.py`` has the reduction."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"


def read(run):
    return anatomy.part_ms(run, "lm_head")
