"""The least time the chip could take for the gate passes of a step's gated
short-convolution layers (``lib/cost_lfm2.py``: the bytes no implementation
avoids, ``[B | C | u]`` read and the result written forward, the four read
and the three cotangents written backward, the forward's once more where the
trace shows the layer's checkpoint running the pass again,
``recompute/shortconv_gate``; the larger of those bytes at HBM bandwidth and
the taps' and gates' FLOPs at the bf16 peak) over the time the part
``shortconv_gate`` took (``step.shortconv_gate_ms``): the same work whatever
implements it.  ``describe`` says which peak bounds it and whether a
recomputed forward was counted.  None where the program has no such scope or
the chip's peaks are unknown."""
from benchmarks.lib import anatomy, cost_lfm2

LAYER, UNIT, SOURCE, MOVES = "step", "%", "device_trace", \
    "tokens_per_s_per_chip"
PART = "shortconv_gate"


def _least(run):
    table = anatomy.table(run)
    if run.peaks is None or not table \
            or not any(key.endswith("/" + PART) for key in table):
        return None
    recomputed = bool(table.get("recompute/" + PART))
    seconds, bound = cost_lfm2.gate_least_time(
        run.cell["config_file"], run.tokens_per_step // run.chips,
        recomputed, run.peaks.flops, run.peaks.hbm_bw)
    return seconds, bound, recomputed


def read(run):
    least = _least(run)
    took = least and anatomy.part_ms(run, PART)
    return 100.0 * least[0] / (took / 1e3) if took else None


def describe(run):
    least = _least(run)
    return least and {"least_ms": 1e3 * least[0], "bound_by": least[1],
                      "recomputed": least[2]}
