"""The part ``shortconv_gate`` of the step's anatomy
(``lib/anatomy_part.py``): a gated short-convolution layer's input gate, its
causal depthwise taps over positions and its output gate, forward, backward
and where the layer's checkpoint runs the forward again."""
from functools import partial

from benchmarks.lib import anatomy_part

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PART = "shortconv_gate"

read = partial(anatomy_part.read, part=PART)
describe = partial(anatomy_part.describe, part=PART)
