"""Seconds from the loop's first pull on the ingest to the first batch in its
hands, on the device: the ingest's start-up (span ``train.first_batch``, a
row of the program's ``device_telemetry.setup_account()``), which
``ingest.data_wait_ms`` at step 0 only partly shows."""
from benchmarks.lib import setup_registry

LAYER, UNIT, SOURCE, MOVES = "ingest", "s", "program_span", "setup_s"


def read(run):
    return setup_registry.row_seconds("train.first_batch")
