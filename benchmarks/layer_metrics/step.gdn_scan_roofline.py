"""The least time the chip could take for the delta-rule scans of a step
(``lib/cost_olmo_hybrid.py``: every linear layer's products a chunk, ``A``
and ``B`` at the causal half, ``T [V | Kbar]`` at the triangular half, ``B
U`` and the three dk x dv products with the state, and the bytes no
implementation avoids, q, k, v, g, beta and o once each way and the chunk
states; the larger of the FLOPs at the bf16 peak and the bytes at HBM
bandwidth) over the time the part ``gdn_scan`` took (``step.gdn_scan_ms``).
The work counts one forward pass, two more for the backward, and one more
where the trace shows the layer's checkpoint running the scan again
(``recompute/gdn_scan``), as ``step.kda_scan_roofline`` counts them: the
same work whatever implements it.  ``describe`` says which peak bounds it
and how many passes were counted.  None where the program has no such scope
or the chip's peaks are unknown."""
from benchmarks.lib import anatomy, cost_olmo_hybrid

LAYER, UNIT, SOURCE, MOVES = "step", "%", "device_trace", \
    "tokens_per_s_per_chip"
PART = "gdn_scan"


def _least(run):
    table = anatomy.table(run)
    if run.peaks is None or not table \
            or not any(key.endswith("/" + PART) for key in table):
        return None
    passes = 4.0 if table.get("recompute/" + PART) else 3.0
    seconds, bound = cost_olmo_hybrid.scan_least_time(
        run.cell["config_file"], run.tokens_per_step // run.chips,
        run.seq_len, passes, run.peaks.flops, run.peaks.hbm_bw)
    return seconds, bound, passes


def read(run):
    least = _least(run)
    took = least and anatomy.part_ms(run, PART)
    return 100.0 * least[0] / (took / 1e3) if took else None


def describe(run):
    least = _least(run)
    return least and {"least_ms": 1e3 * least[0], "bound_by": least[1],
                      "passes": least[2]}
