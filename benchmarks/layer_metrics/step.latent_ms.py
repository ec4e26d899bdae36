"""The part ``latent`` of the step's anatomy (``lib/anatomy_part.py``): what
a latent-attention layer (``models/mla.py``) does between its pre-norm and
the attention kernel, the two down-projections, the latent norms, the two
up-projections, the rotary passes and assembling k, all phases summed.  In a
configuration with a prediction module the module's layer is among them
(seven layers in ``joyai-ep16-s8192``)."""
from functools import partial

from benchmarks.lib import anatomy_part

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PART = "latent"

read = partial(anatomy_part.read, part=PART)
describe = partial(anatomy_part.describe, part=PART)
