"""The part ``shared_expert`` of the step's anatomy
(``lib/anatomy_part.py``): the shared expert of an expert layer
(``models/moe.py``): the dense two-matrix MLP every token passes, and its sum
with the routed part."""
from functools import partial

from benchmarks.lib import anatomy_part

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PART = "shared_expert"

read = partial(anatomy_part.read, part=PART)
describe = partial(anatomy_part.describe, part=PART)
