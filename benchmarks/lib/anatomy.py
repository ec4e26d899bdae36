"""Device time of the train step by the names the program gave its own work.

The program's ``TrainStep`` (``ray_tpu/parallel/train_state.py``) registers
itself in ``ray_tpu.util.device_telemetry`` under the label ``train_step`` and
answers ``anatomy()``: ``{instruction name: (phase, part)}`` of its compiled
module, the phase one of forward | backward | recompute | update from jax's
transform path, the part the innermost ``jax.named_scope`` the models opened
(embed | attn | attn_kernel | mlp | lm_head | optimizer); ``None`` where an
instruction has neither.  The trace's ``XLA Ops`` events carry the same
instruction names, so the self time (``trace_reduce.leaves_and_self_times``)
of the first chip's instructions in the steady stretch can be summed by
either.  Self times of one chip's instructions do not overlap and a container
(``while``) keeps only what its body does not cover, so the groups add up to
the chip's busy time.

A program without such a registry (the parent of the PR that added the
names) gives no anatomy: every reader built on this returns None.
"""

from __future__ import annotations

import sys
from typing import Callable, Dict, List, Optional, Tuple

from benchmarks.lib import trace_reduce

Key = Tuple[Optional[str], Optional[str]]

#: the scopes that make up the attention part: the kernel call is named
#: ``attn_kernel`` inside ``attn``, and anatomy() gives the innermost
ATTN = ("attn", "attn_kernel")


def step_anatomy(run) -> Optional[Dict[str, Key]]:
    """What the run holds (``kinds/train.py`` sets ``run.anatomy`` from
    :func:`of_text`, a test from a recording); else ``anatomy()`` of the
    program's train step, asked once per run however many readers want it
    (it lowers and loads the step again)."""
    if "anatomy" not in run.__dict__:
        run.anatomy = _ask_the_program()
    return run.anatomy


def of_text(hlo_text: str) -> Optional[Dict[str, Key]]:
    """The program's own ``parse_anatomy`` on the compiled text of the
    signature that ran in the window.  ``TrainStep.anatomy()`` keeps the
    first call's, and under a mesh the step's second call can compile anew
    (on four virtual CPU devices its state comes back cut finer than it went
    in): another program, with other names (PERF.md, section 7, PR 30)."""
    module = sys.modules.get("ray_tpu.parallel.train_state")
    parse = getattr(module, "parse_anatomy", None)
    if parse is None:
        return None
    return {name: tuple(key) for name, key in parse(hlo_text).items()}


def _ask_the_program() -> Optional[Dict[str, Key]]:
    telemetry = sys.modules.get("ray_tpu.util.device_telemetry")
    find = getattr(telemetry, "program", None)
    step = find("train_step") if find else None
    if step is None or not hasattr(step, "anatomy"):
        return None
    return {name: tuple(key) for name, key in step.anatomy().items()}


def self_seconds(run) -> Optional[Dict[str, float]]:
    """Per instruction name, self time summed over the first chip's steady
    stretch."""
    if "self_seconds" not in run.__dict__:
        run.self_seconds = None
        if run.trace and run.trace.first and run.steady:
            lo, hi = run.steady[:2]
            _, run.self_seconds = trace_reduce.leaves_and_self_times(
                trace_reduce.in_window(run.trace.first.ops, lo, hi))
    return run.self_seconds


def _classified(run) -> Optional[List[Tuple[str, Key, float]]]:
    """(instruction, its (phase, part), self seconds) over the steady
    stretch; an instruction the anatomy does not know is (None, None)."""
    anatomy, seconds = step_anatomy(run), self_seconds(run)
    if anatomy is None or not seconds:
        return None
    return [(name, anatomy.get(name, (None, None)), s)
            for name, s in seconds.items()]


def ms_per_step(run, wanted: Callable[[Key], bool]) -> Optional[float]:
    """Milliseconds a step, on the first chip, in instructions whose
    (phase, part) ``wanted`` accepts."""
    rows = _classified(run)
    if rows is None:
        return None
    return 1e3 * sum(s for _, key, s in rows if wanted(key)) / run.steady[2]


def phase_ms(run, phase: Optional[str]) -> Optional[float]:
    return ms_per_step(run, lambda key: key[0] == phase)


def part_ms(run, *parts: str) -> Optional[float]:
    return ms_per_step(run, lambda key: key[1] in parts)


def table(run) -> Optional[Dict[str, float]]:
    """ms a step by ``phase/part`` (``-`` for None), largest first: what a
    run's report keeps of the whole breakdown."""
    rows = _classified(run)
    if rows is None:
        return None
    out: Dict[str, float] = {}
    for _, (phase, part), s in rows:
        key = f"{phase or '-'}/{part or '-'}"
        out[key] = out.get(key, 0.0) + 1e3 * s / run.steady[2]
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def longest(run, wanted: Callable[[Key], bool], n: int = 8
            ) -> Optional[List[List]]:
    """The ``n`` instructions ``wanted`` accepts with the most self time, as
    [name, result type, ms a step]."""
    rows = _classified(run)
    if rows is None:
        return None
    detail = {e.name: e.detail for e in run.trace.first.ops}
    top = sorted(((s, name) for name, key, s in rows if wanted(key)),
                 reverse=True)[:n]
    return [[name, detail.get(name, ""), 1e3 * s / run.steady[2]]
            for s, name in top]


def span_note(run, key: str) -> Optional[Dict]:
    """Median and longest of a per-step host counter of the profiler rows,
    for the report: a mean that a single hiccup moved shows here."""
    values = [r[key] for r in run.profiler_rows if key in r]
    if not values:
        return None
    longest = max(range(len(values)), key=values.__getitem__)
    return {"mean_ms": 1e3 * sum(values) / len(values),
            "median_ms": 1e3 * trace_reduce.median(values),
            "longest_ms": 1e3 * values[longest], "longest_at_row": longest,
            "rows": len(values)}
