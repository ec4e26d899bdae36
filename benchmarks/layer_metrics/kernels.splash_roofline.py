"""The least time the chip could take for the splash calls it ran over the
time they took, each call charged for itself: its kind is the one of the
family's ``attention_calls`` (``lib/family.py``) that claims the call's line
of the compiled step (the shapes the kernel is handed, a named scope where
the kind states one), its rows are that line's too, and its FLOPs and bytes
are ``lib/cost.py:attention_call_cost``'s over that kind's own mask area,
heads and head dimensions, over the peaks table.  A call whose name holds
``fwd`` is a forward, any other a fused backward.  So a step whose layers run
two masks, or one mask in two calls, reads the share of what it had to do,
and a splash call that no kind claims is an error that names it.

The kernel computes whole blocks, so a mask that cuts through blocks has a
ceiling under 100: a block-diffusion row at S=8192 in blocks of 1024 visits
80 blocks a head that hold 83.9 M pairs for the mask's 67.1 M, 80 % at the
most (89 % in blocks of 512, until PR 42).

``describe``: for each kind and direction the calls a step, their time, their
least time, their own share and which peak bounds them."""
import collections

from benchmarks.lib import cost, family

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", \
    "tokens_per_s_per_chip"


def _least(run, event):
    """(kind's name and direction, least seconds, which peak bounds it) of
    one event's call."""
    call, rows = family.kind_of(
        run.attention_calls, run.seq_len, event.name,
        run.hlo["operands"][event.name], run.hlo["mosaic"][event.name])
    way = "fwd" if "fwd" in event.name else "bwd"
    flops, nbytes = cost.attention_call_cost(way, rows, call, run.seq_len)
    return (f"{call.name}.{way}",) + cost.least_time(
        flops, nbytes, run.peaks.flops, run.peaks.hbm_bw)


def read(run):
    events = run.kernel_events("splash")
    if not events or run.peaks is None:
        return None
    return 100.0 * sum(_least(run, e)[1] for e in events) \
        / sum(e.dur for e in events)


def describe(run):
    events = run.kernel_events("splash")
    if not events or run.peaks is None:
        return None
    kinds = collections.defaultdict(list)
    for e in events:
        name, least, bound = _least(run, e)
        kinds[name].append((e.dur, least, bound))
    steps, notes = run.steady[2], {}
    for name, rows in sorted(kinds.items()):
        dur, least = sum(r[0] for r in rows), sum(r[1] for r in rows)
        notes[name] = {
            "calls_a_step": len(rows) / steps, "ms_a_step": 1e3 * dur / steps,
            "least_ms_a_step": 1e3 * least / steps,
            "roofline": 100.0 * least / dur, "bound_by": rows[0][2]}
    return notes
