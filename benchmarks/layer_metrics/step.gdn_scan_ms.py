"""The part ``gdn_scan`` of the step's anatomy (``lib/anatomy_part.py``): the
chunked delta-rule scan with one decay a head (``ops/gdn.py``: the q.k and
k.k products and their decays, the triangular system a chunk, the scan over
the chunk states and the outputs), whatever implements it, XLA fusions or a
Mosaic call."""
from functools import partial

from benchmarks.lib import anatomy_part

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PART = "gdn_scan"

read = partial(anatomy_part.read, part=PART)
describe = partial(anatomy_part.describe, part=PART)
