"""KiB a step that ``device_put_batch`` sent to the device, from the
program's own ``StepProfiler`` rows (counter ``h2d_bytes``); mean over the
window's steps.  Two int32 columns are 8 bytes a token.  ``describe`` lists
the distinct per-step values."""
LAYER, UNIT, SOURCE, MOVES = "ingest", "KiB/step", "program_counter", \
    "tokens_per_s_per_chip"


def read(run):
    rows = run.profiler_rows
    if not rows or "h2d_bytes" not in rows[0]:
        return None
    return sum(r["h2d_bytes"] for r in rows) / len(rows) / 1024


def describe(run):
    seen = sorted({r["h2d_bytes"] for r in run.profiler_rows
                   if "h2d_bytes" in r})
    return seen and {
        "distinct_bytes_per_step": seen,
        "bytes_per_token": [b / run.tokens_per_step for b in seen]}
