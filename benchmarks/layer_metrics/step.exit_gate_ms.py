"""The part ``exit_gate`` of the step's anatomy (``lib/anatomy_part.py``): a
looped stack's exit gate (``models/looped.py``), the gate's product with each
pass's normed state, the sigmoids, the exit distribution, its entropy and the
combination of the passes' cross-entropies into the loss, forward and
backward.  The passes' heads are ``step.lm_head_ms``'s."""
from functools import partial

from benchmarks.lib import anatomy_part

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PART = "exit_gate"

read = partial(anatomy_part.read, part=PART)
describe = partial(anatomy_part.describe, part=PART)
