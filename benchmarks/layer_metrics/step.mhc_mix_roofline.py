"""The least time the chip could take for a step's reads and writes of the
residual's streams (``lib/cost_xing.py:mix_bytes``: the bytes no
implementation avoids, a sub-layer's forward reads ``X`` twice, writes ``u``,
reads ``y`` and writes ``X'``, 14 arrays of (tokens, hidden) at four streams,
its backward 27, and the read's 5 once more where the trace shows the
layer's checkpoint running it again, ``recompute/mhc_mix``; at the chip's HBM
bandwidth) over the time the hyper-connections took, all three parts
(``step.mhc_ms``: ``mhc`` + ``mhc_maps`` + ``mhc_mix``): the same work
whatever implements it.  Over all three and not over ``step.mhc_mix_ms``
alone, because the compiler does part of the mix's work under the maps'
scope (the sum of the streams' cotangents over their readers rides in the
maps' backward products as their epilogue: over ``mhc_mix`` alone the share
read 138 % on the chip), and because the maps need no bytes of their own in
the least form (a pass that makes the maps while it reads ``X`` for the
read): what the share leaves to 100 % is what one pass a direction could
win.  ``describe`` gives the least milliseconds, the three parts' time and
whether a recomputed read was counted.  None where the program has no such
scope or the chip's peaks are unknown."""
from benchmarks.lib import anatomy, cost_xing

LAYER, UNIT, SOURCE, MOVES = "step", "%", "device_trace", \
    "tokens_per_s_per_chip"
PART = "mhc_mix"
PARTS = ("mhc", "mhc_maps", "mhc_mix")


def _least(run):
    table = anatomy.table(run)
    if run.peaks is None or not table \
            or not any(key.endswith("/" + PART) for key in table):
        return None
    recomputed = bool(table.get("recompute/" + PART))
    nbytes = cost_xing.mix_bytes(run.cell["config_file"],
                                 run.tokens_per_step // run.chips, recomputed)
    return nbytes / run.peaks.hbm_bw, recomputed


def read(run):
    least = _least(run)
    took = least and anatomy.part_ms(run, *PARTS)
    return 100.0 * least[0] / (took / 1e3) if took else None


def describe(run):
    least = _least(run)
    return least and {"least_ms": 1e3 * least[0], "bound_by": "memory",
                      "over_ms": anatomy.part_ms(run, *PARTS),
                      "recomputed": least[1]}
