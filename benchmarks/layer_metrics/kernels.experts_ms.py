"""Per step, the summed device time of the grouped-matmul kernel's Mosaic
calls on the first chip (the megablox ``gmm`` and ``tgmm``: per layer three
forward products, the recomputation of two of them under remat, gate and up
(the backward does not read the down-projection's output since PR 29), three
dx and three dW: eleven calls), by the instruction names the compiled step
and the trace share.  ``describe`` gives the time by call."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"


def read(run):
    events = run.kernel_events("gmm")
    if not events:
        return None
    return 1e3 * sum(e.dur for e in events) / run.steady[2]


def describe(run):
    by_call = {}
    for e in run.kernel_events("gmm"):
        by_call[e.name] = by_call.get(e.name, 0.0) \
            + 1e3 * e.dur / run.steady[2]
    return by_call or None
