"""The program's own ``StepProfiler`` bucket ``h2d`` (host-to-device transfer
dispatch in ``DeviceBatchIterator``), mean over the window's steps."""
LAYER, UNIT, SOURCE, MOVES = "ingest", "ms/step", "program_span", \
    "tokens_per_s_per_chip"


def read(run):
    return run.bucket_ms("h2d")
