"""The part ``gdn_conv`` of the step's anatomy (``lib/anatomy_part.py``): the
three causal depthwise convolutions over positions of a gated-delta-net
layer (q, k and v) and the silu."""
from functools import partial

from benchmarks.lib import anatomy_part

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PART = "gdn_conv"

read = partial(anatomy_part.read, part=PART)
describe = partial(anatomy_part.describe, part=PART)
