"""Per step, the time a collective instruction runs on a chip while no other
instruction does there; the worst chip, over the trace's steady stretch.
Collective are the instructions named as one (``all-gather``,
``async-collective-start`` / ``-done``, ...) and the fusions that are one and
nothing else (``kind=kCustom, calls=%all-reduce-scatter``).  A fusion of
arithmetic that carries an asynchronous collective along
(``calls=%async_collective_fusion``) is not: the transfer runs in its shadow,
and what of it outlasts the arithmetic shows in ``collectives.carrier_ms``,
not here.  So this is the time that is certainly exposed, a lower figure."""
from benchmarks.lib import trace_reduce

LAYER, UNIT, SOURCE, MOVES = "collectives", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"


def read(run):
    if not run.steady or not run.trace.devices:
        return None
    lo, hi, steps = run.steady[:3]
    return 1e3 * max(trace_reduce.exposed_collective_seconds(d.ops, lo, hi)
                     for d in run.trace.devices.values()) / steps
