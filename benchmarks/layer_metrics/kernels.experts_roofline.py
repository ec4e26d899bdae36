"""The least time the chip could take for the model's nine grouped products a
layer (three matrices, each forward, dx and dW: ``18 x rows x hidden x
width`` FLOPs and one read of every expert's matrix per product plus its rows
in and out, ``lib/cost_moe.py``, over the peaks table) over the time the
grouped-matmul kernel's calls took (``kernels.experts_ms``).  What the
program recomputes under remat is its own choice and counts against it, as in
``step.mfu``.  ``describe`` says which peak bounds a product."""
from benchmarks.lib import cost, cost_moe

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", \
    "tokens_per_s_per_chip"


def _least(run):
    """(seconds a step, which peak bounds it), or None where the run has no
    peaks table or its configuration no experts."""
    config = run.cell["config_file"]
    if run.peaks is None or "num_experts" not in config:
        return None
    flops, nbytes = cost_moe.expert_layer_cost(
        config, run.tokens_per_step // run.chips)
    return cost.least_time(flops, nbytes, run.peaks.flops, run.peaks.hbm_bw)


def read(run):
    events, least = run.kernel_events("gmm"), _least(run)
    if not events or least is None:
        return None
    return 100.0 * least[0] * run.steady[2] / sum(e.dur for e in events)


def describe(run):
    least = _least(run)
    return least and {"bound_by": least[1],
                      "least_ms_per_step": 1e3 * least[0]}
