"""Per step, the summed device time of the splash attention kernel's Mosaic
calls (forward, any re-forward under remat, backward) on the first chip, by
the instruction names the compiled step and the trace share."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"


def read(run):
    events = run.kernel_events("splash")
    if not events:
        return None
    return 1e3 * sum(e.dur for e in events) / run.steady[2]
