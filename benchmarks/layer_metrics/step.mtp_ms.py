"""Per step, the self time of the first chip's instructions in the trace's
steady stretch that the program's ``TrainStep.anatomy()`` puts in the
multi-token-prediction module's own parts (``models/hybrid.py``), all phases
summed: ``mtp`` (the two norms, the join with the next token's embedding and
``w_eh``) and ``mtp_head`` (its final norm and its pass through the shared
head).  The module's block, one more latent-attention and expert layer,
opens its kinds' own scopes and is counted with the layers
(``step.attn_ms``, ``step.latent_ms``, ``step.mlp_ms``).  ``describe`` keeps
the two parts apart, by phase, and gives the step's two losses (the step
counters ``loss_main`` and ``loss_mtp`` of the ``StepProfiler`` rows) at the
window's first and last row.  None where the program has no such scope."""
from benchmarks.lib import anatomy

LAYER, UNIT, SOURCE, MOVES = "step", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"
PARTS = ("mtp", "mtp_head")


def _by_phase(run):
    table = anatomy.table(run) or {}
    return {key: ms for key, ms in table.items()
            if key.split("/")[1] in PARTS}


def read(run):
    return anatomy.part_ms(run, *PARTS) if _by_phase(run) else None


def describe(run):
    by_phase = _by_phase(run)
    if not by_phase:
        return None
    rows = [r for r in run.profiler_rows if "loss_mtp" in r]
    return {"by_phase": by_phase,
            "losses": {name: [rows[0][name], rows[-1][name]]
                       for name in ("loss_main", "loss_mtp")} if rows
            else None}
