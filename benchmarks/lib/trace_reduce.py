"""From a ``jax.profiler`` trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  On a TPU the trace
holds one plane per chip, ``/device:TPU:<n>``; its line ``XLA Modules`` has one
event per executed program and its line ``XLA Ops`` one event per executed HLO
instruction, containers (``while``, ``conditional``, ``call``) enclosing their
bodies' events; an instruction's event is named by its whole text
(``%fusion.440 = bf16[8,1024,4096]{...} fusion(...)``), cut here to the
instruction's name and result type.  ``Async XLA Ops`` holds the spans of
asynchronous copies and collectives from ``-start`` to ``-done`` and is not
read: what the chip waits for shows as the ``-done`` on ``XLA Ops``.  The host's spans (``jax.profiler.TraceAnnotation``) are on
the thread lines of ``/host:CPU``, on the same clock.  All times below are
seconds from the start of the profile.

Everything after ``load`` is a pure function of event lists, tested on hand-
made lists and on recorded traces (``benchmarks/tests/test_trace_reduce.py``).
"""

from __future__ import annotations

import gzip
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
INSTRUCTION = re.compile(r"^%?([\w.\-]+) = (.*?) [a-z\-]+\(")
LAYOUT = re.compile(r"\{[^}]*\}")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|async-collective)")
COLLECTIVE_FUSION = re.compile(
    r"calls=%?(all-reduce-scatter|all-gather|all-reduce|reduce-scatter"
    r"|all-to-all|collective-permute)")
CARRIER_FUSION = re.compile(r"calls=%?async_collective_fusion")


@dataclass
class Event:
    name: str
    start: float
    dur: float
    #: an instruction's result type without layouts; else empty
    detail: str = ""
    #: a fusion that is a collective and nothing else (``kind=kCustom,
    #: calls=%all-reduce-scatter.5``, how the TPU compiler emits a
    #: reduce-scatter); its name does not say so
    collective: bool = False
    #: a fusion of arithmetic (``kind=kOutput, calls=%async_collective_
    #: fusion.599``: a matmul, as a rule) that carries an asynchronous
    #: collective along between its ``-start`` and its ``-done``: the
    #: transfer runs in its shadow, and the trace does not say which of the
    #: two set its length
    carrier: bool = False

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Device:
    ordinal: int
    modules: List[Event] = field(default_factory=list)
    ops: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    devices: Dict[int, Device]
    host_spans: List[Event]
    #: every plane's line names with event counts, for looking at a trace
    layout: Dict[str, Dict[str, int]]

    @property
    def first(self) -> Optional[Device]:
        """The chip with the lowest ordinal, whose clock sets the window."""
        return self.devices[min(self.devices)] if self.devices else None


def load(path: str, span_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, spans, layout = {}, [], {}
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        lines = layout.setdefault(plane.name, {})
        for line in plane.lines:
            events = [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                      for e in line.events]
            lines[line.name] = len(events)
            if match and line.name == OPS_LINE:
                for e in events:
                    parsed = INSTRUCTION.match(e.name)
                    if parsed:
                        e.collective = bool(COLLECTIVE_FUSION.search(e.name))
                        e.carrier = bool(CARRIER_FUSION.search(e.name))
                        e.name = parsed.group(1)
                        e.detail = LAYOUT.sub("", parsed.group(2))[:60]
            if match:
                dev = devices.setdefault(int(match.group(1)),
                                         Device(int(match.group(1))))
                if line.name == MODULES_LINE:
                    dev.modules = events
                elif line.name == OPS_LINE:
                    dev.ops = events
            elif plane.name.startswith("/host:"):
                spans += [e for e in events if e.name.startswith(span_prefix)]
    spans.sort(key=lambda e: e.start)
    return Trace(devices, spans, layout)


# ------------------------------------------------------------ interval sets
def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def spans_of(events: Iterable[Event]) -> List[Interval]:
    return [(e.start, e.end) for e in events]


# ------------------------------------------------------------------- steps
def step_module(modules: Sequence[Event], hint: str = "step") -> Optional[str]:
    """The name of the step program: among module names that hold ``hint``
    (the jitted function is called ``step``), else among all, the one with
    the most device time."""
    by_name: Dict[str, float] = {}
    for e in modules:
        by_name[e.name] = by_name.get(e.name, 0.0) + e.dur
    hinted = {n: t for n, t in by_name.items() if hint in n}
    pool = hinted or by_name
    return max(pool, key=pool.get) if pool else None


def steady_window(modules: Sequence[Event], name: str
                  ) -> Optional[Tuple[float, float, int, List[float]]]:
    """(lo, hi, whole steps, periods): from the start of the second execution
    of ``name`` in the trace to the start of the last, which leaves out the
    refill after ``start_trace`` stalled the host and the drain before
    ``stop_trace``.  None with fewer than three executions."""
    starts = sorted(e.start for e in modules if e.name == name)
    if len(starts) < 3:
        return None
    inner = starts[1:]
    periods = [b - a for a, b in zip(inner, inner[1:])]
    return inner[0], inner[-1], len(periods), periods


def median(values: Sequence[float]) -> float:
    s = sorted(values)
    mid = len(s) // 2
    return s[mid] if len(s) % 2 else 0.5 * (s[mid - 1] + s[mid])


# --------------------------------------------------------------------- ops
def leaves_and_self_times(ops: Sequence[Event]
                          ) -> Tuple[List[Event], Dict[str, float]]:
    """Events that enclose no other event, and per name the time an event
    ran minus the time of the events directly inside it."""
    order = sorted(ops, key=lambda e: (e.start, -e.dur))
    self_time: Dict[str, float] = {}
    leaves: List[Event] = []
    stack: List[Tuple[Event, List[float]]] = []  # event, [children's time]

    def close():
        event, children = stack.pop()
        self_time[event.name] = self_time.get(event.name, 0.0) \
            + max(event.dur - children[0], 0.0)
        if children[0] == 0.0:
            leaves.append(event)
        if stack:
            stack[-1][1][0] += event.dur

    for e in order:
        # Inside means wholly inside; an overlapping neighbour is a sibling.
        while stack and (e.start >= stack[-1][0].end
                         or e.end > stack[-1][0].end + 1e-9):
            close()
        stack.append((e, [0.0]))
    while stack:
        close()
    return leaves, self_time


def in_window(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [e for e in events if lo <= e.start < hi]


def busy_seconds(ops: Sequence[Event], lo: float, hi: float) -> float:
    return total(clip(merge(spans_of(ops)), lo, hi))


def idle_gaps(ops: Sequence[Event], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], clip(merge(spans_of(ops)), lo, hi))


def is_collective(event: Event) -> bool:
    return event.collective or bool(COLLECTIVE.match(event.name))


def exposed_collective_seconds(ops: Sequence[Event], lo: float,
                               hi: float) -> float:
    """Time in the window during which a collective instruction runs on the
    device and no other instruction does.  A carrier is another instruction:
    what it hides is hidden."""
    leaves, _ = leaves_and_self_times(in_window(ops, lo, hi))
    coll = merge(spans_of(e for e in leaves if is_collective(e)))
    compute = merge(spans_of(e for e in leaves if not is_collective(e)))
    return total(clip(subtract(coll, compute), lo, hi))


def carrier_seconds(ops: Sequence[Event], lo: float, hi: float) -> float:
    """Time in the window inside fusions that carry an asynchronous
    collective beside their own arithmetic."""
    leaves, _ = leaves_and_self_times(in_window(ops, lo, hi))
    return total(clip(merge(spans_of(e for e in leaves if e.carrier)),
                      lo, hi))


def top_ops(ops: Sequence[Event], lo: float, hi: float, n: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``n`` instruction families with the most self time in the window.
    A family is the instructions that share a name up to its number and a
    result type (an unrolled model runs ``convert_reduce_fusion.49`` to
    ``.71``, one per layer); its label is ``name xCOUNT result-type``, the
    count being that of distinct instructions."""
    inside = in_window(ops, lo, hi)
    _, self_time = leaves_and_self_times(inside)
    detail = {e.name: e.detail for e in inside}
    families: Dict[Tuple[str, str], List[float]] = {}
    for name, seconds in self_time.items():
        key = (re.sub(r"\.\d+$", "", name), detail[name])
        family = families.setdefault(key, [0, 0.0])
        family[0] += 1
        family[1] += seconds
    top = sorted(families.items(), key=lambda kv: -kv[1][1])[:n]
    return [(f"{base} x{count} {kind}".strip(), seconds)
            for (base, kind), (count, seconds) in top]


def label_gaps(gaps: Sequence[Interval], host_spans: Sequence[Event],
               n: int = 5) -> List[Tuple[str, float]]:
    """The ``n`` longest gaps, each named by the host span that covers most
    of it (``host:unspanned`` where none does)."""
    out = []
    for lo, hi in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, cover = "host:unspanned", 0.0
        for s in host_spans:
            c = min(s.end, hi) - max(s.start, lo)
            if c > cover:
                best, cover = s.name, c
        out.append((best, hi - lo))
    return out
