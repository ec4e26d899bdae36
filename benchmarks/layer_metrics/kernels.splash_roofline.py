"""The least time the chip could take for the splash calls it ran (each call's
causal flash-attention FLOPs and bytes from its shapes, ``lib/cost.py``, over
the peaks table; a call whose name holds ``fwd`` is a forward, any other a
backward) over the time they took.  ``describe`` says which peak bounds each
kind of call, for the run's report."""
from benchmarks.lib import cost

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", \
    "tokens_per_s_per_chip"


def _least(run, event):
    heads, head_dim = run.attention_heads
    kind = "fwd" if "fwd" in event.name else "bwd"
    flops, nbytes = cost.attention_call_cost(
        kind, run.attention_batch_per_chip, heads, run.seq_len, head_dim)
    return cost.least_time(flops, nbytes, run.peaks.flops, run.peaks.hbm_bw)


def read(run):
    events = run.kernel_events("splash")
    if not events or run.peaks is None:
        return None
    return 100.0 * sum(_least(run, e)[0] for e in events) \
        / sum(e.dur for e in events)


def describe(run):
    if run.peaks is None:
        return None
    return {("fwd" if "fwd" in e.name else "bwd") + "_bound_by":
            _least(run, e)[1] for e in run.kernel_events("splash")}
