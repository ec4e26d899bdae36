"""The part ``mhc_maps`` of the step's anatomy (``lib/anatomy_part.py``): a
sub-layer's stream maps under hyper-connections (``models/streams.py``), the
norm over every stream's lanes, the product with ``phi``, the two sigmoids
and the Sinkhorn turns, forward, backward and where the layer's checkpoint
runs them again (not where ``ops/remat.py`` kept the maps)."""
from functools import partial

from benchmarks.lib import anatomy_part

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PART = "mhc_maps"

read = partial(anatomy_part.read, part=PART)
describe = partial(anatomy_part.describe, part=PART)
