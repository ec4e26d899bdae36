"""The part ``ssm_scan`` of the step's anatomy (``lib/anatomy_part.py``): the
chunked state-space scan (``ops/ssd.py``: the products inside a chunk, the
chunk states, the scan over them and the decays), whatever implements it,
XLA fusions or a Mosaic call."""
from functools import partial

from benchmarks.lib import anatomy_part

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PART = "ssm_scan"

read = partial(anatomy_part.read, part=PART)
describe = partial(anatomy_part.describe, part=PART)
