"""Seconds of the train step's first call spent reading its executable from
the persistent compile cache (jax's ``cache_retrieval_time_sec``; a part of
the record's ``compile_s``, 0 on a miss), from the ``cache_load_s`` of the
first-call record (span ``train.first_call``)."""
from benchmarks.lib import setup_registry

LAYER, UNIT, SOURCE, MOVES = "step", "s", "program_span", "setup_s"


def read(run):
    return setup_registry.first_call().get("cache_load_s")
