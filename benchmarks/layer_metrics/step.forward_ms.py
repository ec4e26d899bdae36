"""Per step, the self time (``trace_reduce.leaves_and_self_times``) of the
first chip's instructions in the trace's steady stretch that the program's
``TrainStep.anatomy()`` puts in the phase ``forward`` (jax's transform path holds a ``jvp(`` and
no ``transpose(jvp(``).
``lib/anatomy.py`` has the reduction."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"


def read(run):
    return anatomy.phase_ms(run, "forward")
