"""Seconds between the process's start and the end of the first steady step
that no span of the set-up covers (``unspanned_s`` of the program's
``device_telemetry.setup_account()``): the interpreter's start, the imports
of jax and ``ray_tpu``, the accelerator runtime coming up, and what the
harness runs between the program's spans.  ``describe`` lists the gaps by the
rows they lie between, the rows' seconds and ``to_first_step_s``."""
from benchmarks.lib import setup_registry

LAYER, UNIT, SOURCE, MOVES = "trainer", "s", "program_span", "setup_s"


def read(run):
    return (setup_registry.account() or {}).get("unspanned_s")


def describe(run):
    account = setup_registry.account()
    if account is None:
        return None
    return {"to_first_step_s": account["to_first_step_s"],
            "spanned_s": account["spanned_s"],
            "start_from": account["start_from"],
            "closed_by": (account["closed"] or {}).get("by"),
            "gaps": account["gaps"],
            "rows_s": [[r["name"], r["end"] - r["start"]]
                       for r in account["rows"]]}
