"""The part ``ssm_conv`` of the step's anatomy (``lib/anatomy_part.py``): the
causal depthwise convolution over positions of a Mamba-2 layer, its bias and
the silu."""
from functools import partial

from benchmarks.lib import anatomy_part

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PART = "ssm_conv"

read = partial(anatomy_part.read, part=PART)
describe = partial(anatomy_part.describe, part=PART)
