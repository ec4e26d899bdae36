"""Model FLOP/s utilization on the device clock: the benchmark's FLOPs per
step (``lib/cost.py``: matmul parameters and causal attention, nothing
recomputed) over the median device step time and the chips' bf16 peak."""
LAYER, UNIT, SOURCE, MOVES = "step", "%", "device_trace", \
    "tokens_per_s_per_chip"


def read(run):
    if not run.step_seconds or run.peaks is None:
        return None
    return 100.0 * run.flops_per_step / run.step_seconds \
        / (run.chips * run.peaks.flops)
