"""Executables the persistent compile cache did not hold during set-up: the
program's compile records (``device_telemetry.compile_records()``, every
label and the unlabelled) up to the end of the first steady step whose
``cache`` is ``miss``.  0 on a warm run: the figure that says whether a
``setup_s`` was a cold one.  ``describe`` counts the answers by label."""
import collections

from benchmarks.lib import setup_registry

LAYER, UNIT, SOURCE, MOVES = "step", "count", "program_counter", "setup_s"


def read(run):
    records = setup_registry.setup_compiles()
    return None if records is None else sum(
        r.get("cache") == "miss" for r in records)


def describe(run):
    answers = collections.Counter(
        f"{r['label']}:{r.get('cache')}"
        for r in setup_registry.setup_compiles() or ())
    return dict(answers) or None
