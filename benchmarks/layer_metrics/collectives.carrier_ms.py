"""Per step, the time inside fusions of arithmetic that carry an asynchronous
collective along (``kind=kOutput, calls=%async_collective_fusion``: the
matmul that runs between a gather's ``-start`` and its ``-done``); the worst
chip, over the trace's steady stretch.  A carrier lasts as long as the longer
of its arithmetic and the transfer in its shadow, and the trace does not say
which: ``collectives.exposed_ms`` plus this is the most that collectives can
cost a step, and a gather that gets hidden moves time from there to here."""
from benchmarks.lib import trace_reduce

LAYER, UNIT, SOURCE, MOVES = "collectives", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"


def read(run):
    if not run.steady or not run.trace.devices:
        return None
    lo, hi, steps = run.steady[:3]
    return 1e3 * max(trace_reduce.carrier_seconds(d.ops, lo, hi)
                     for d in run.trace.devices.values()) / steps
