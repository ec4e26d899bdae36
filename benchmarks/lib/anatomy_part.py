"""A per-layer metric that is one part of the step's anatomy and nothing
else (``layer_metrics/step.ssm_scan_ms.py`` and its like): per step, the self
time of the first chip's instructions in the trace's steady stretch that the
program's ``TrainStep.anatomy()`` puts in that part, all phases summed.
``describe``: by phase, and the longest instructions.  Both None where the
program has no such scope."""
from benchmarks.lib import anatomy


def _by_phase(run, part: str):
    table = anatomy.table(run) or {}
    return {key: ms for key, ms in table.items()
            if key.endswith("/" + part)}


def read(run, part: str):
    return anatomy.part_ms(run, part) if _by_phase(run, part) else None


def describe(run, part: str):
    by_phase = _by_phase(run, part)
    return by_phase and {
        "by_phase": by_phase,
        "longest": anatomy.longest(run, lambda key: key[1] == part)}
