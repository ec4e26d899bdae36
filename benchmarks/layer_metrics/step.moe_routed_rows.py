"""The (position, expert) pairs a layer a step that reached a routed expert
this chip holds, in a model whose expert layers stand among layers of other
kinds: the step counter ``moe_rows`` of the ``StepProfiler`` rows
(``models/moe.py``'s group sizes, the held slice; ``models/hybrid.py`` stacks
the expert layers'), summed over the held experts, mean over the window's
rows and the expert layers.  Under an even router it is tokens x experts a
token x held / published (6144 in ``nemotron-ep16-s8192``).  ``describe``:
per expert layer the least, mean and most over the window, and the pairs a
layer sorts.  None where the rows carry no ``moe_rows`` or the
configuration spells out no pattern of layer kinds."""
from benchmarks.lib import device_rows

LAYER, UNIT, SOURCE, MOVES = "step", "count", "program_counter", \
    "tokens_per_s_per_chip"


def read(run):
    if "hybrid_override_pattern" not in run.cell["config_file"]:
        return None
    held = device_rows.held_rows(run)
    return None if held is None else float(held.mean())


def describe(run):
    if "hybrid_override_pattern" not in run.cell["config_file"]:
        return None
    held = device_rows.held_rows(run)
    if held is None:
        return None
    return {"rows": held.shape[0],
            "pairs_a_layer": device_rows.pairs_per_layer(run),
            "per_layer": [{"min": int(column.min()),
                           "mean": float(column.mean()),
                           "max": int(column.max())} for column in held.T]}
