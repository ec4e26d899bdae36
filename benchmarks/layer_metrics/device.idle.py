"""1 - (union of the intervals in which an instruction ran) / window, on the
chip where that is worst, over the trace's steady stretch."""
from benchmarks.lib import trace_reduce

LAYER, UNIT, SOURCE, MOVES = "device", "%", "device_trace", \
    "tokens_per_s_per_chip"


def read(run):
    if not run.steady or not run.trace.devices:
        return None
    lo, hi = run.steady[:2]
    busy = min(trace_reduce.busy_seconds(d.ops, lo, hi)
               for d in run.trace.devices.values())
    return 100.0 * (1.0 - busy / (hi - lo))
