"""Host time of one ``train.report`` call (the loss stays a device array),
from the benchmark's own span around it; median over the window's steps."""
LAYER, UNIT, SOURCE, MOVES = "trainer", "ms/step", "host_clock", \
    "tokens_per_s_per_chip"


def read(run):
    spans = sorted(run.host_spans.get("report", []))
    return 1e3 * spans[len(spans) // 2] if spans else None
