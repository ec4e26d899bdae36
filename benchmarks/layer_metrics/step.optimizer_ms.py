"""Per step, the self time of the first chip's instructions in the trace's
steady stretch that the program's ``TrainStep.anatomy()`` puts in the phase
``update``: everything under the ``optimizer`` scope (``optimizer.update``,
gradient clipping inside the optax chain, ``apply_updates``).
``lib/anatomy.py`` has the reduction."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"


def read(run):
    return anatomy.phase_ms(run, "update")
