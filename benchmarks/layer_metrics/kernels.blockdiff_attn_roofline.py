"""The least time the chip could take for the splash calls of a
block-diffusion cell (each call's FLOPs over the mask's own area, S^2 + S x
block_length pairs a head, and its bytes, ``lib/cost_sdar.py``, over the
peaks table; a call whose name holds ``fwd`` is a forward, any other a fused
backward) over the time they took.  The kernel works in 512 x 512 blocks and
computes the whole of a block the mask cuts through, so the blocks it visits
hold 12 % more pairs than the mask allows at S=8192: a share of 89 % is the
most this geometry can read.  ``describe`` says which peak bounds each kind
of call."""
from benchmarks.lib import cost, cost_sdar

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", \
    "tokens_per_s_per_chip"


def _least(run, event):
    kind = "fwd" if "fwd" in event.name else "bwd"
    flops, nbytes = cost_sdar.attention_call_cost(
        kind, run.cell["config_file"], run.attention_batch_per_chip,
        run.seq_len)
    return cost.least_time(flops, nbytes, run.peaks.flops, run.peaks.hbm_bw)


def read(run):
    events = run.kernel_events("splash")
    if not events or run.peaks is None \
            or "block_length" not in run.cell["config_file"]:
        return None
    return 100.0 * sum(_least(run, e)[0] for e in events) \
        / sum(e.dur for e in events)


def describe(run):
    if run.peaks is None or "block_length" not in run.cell["config_file"]:
        return None
    return {("fwd" if "fwd" in e.name else "bwd") + "_bound_by":
            _least(run, e)[1] for e in run.kernel_events("splash")}
