"""The part ``mhc_mix`` of the step's anatomy (``lib/anatomy_part.py``): the
read that mixes a residual's streams into a sub-layer's input and the write
that mixes them among themselves and adds the sub-layer's output to each
(``models/streams.py``), forward, backward and where the layer's checkpoint
runs the read again."""
from functools import partial

from benchmarks.lib import anatomy_part

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PART = "mhc_mix"

read = partial(anatomy_part.read, part=PART)
describe = partial(anatomy_part.describe, part=PART)
