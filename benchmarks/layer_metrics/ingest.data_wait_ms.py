"""The program's own ``StepProfiler`` bucket ``data_wait`` (blocked on the
input pipeline), mean over the window's steps."""
LAYER, UNIT, SOURCE, MOVES = "ingest", "ms/step", "program_span", \
    "tokens_per_s_per_chip"


def read(run):
    return run.bucket_ms("data_wait")
