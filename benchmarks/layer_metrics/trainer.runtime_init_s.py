"""Seconds inside ``ray_tpu.init()`` (span ``runtime.init``, a row of the
program's ``device_telemetry.setup_account()``)."""
from benchmarks.lib import setup_registry

LAYER, UNIT, SOURCE, MOVES = "trainer", "s", "program_span", "setup_s"


def read(run):
    return setup_registry.row_seconds("runtime.init")
