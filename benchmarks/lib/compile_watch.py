"""What jax compiled, and what the compiled step holds.

Copied from ``chip_smoke.py`` (PR 21), where both were found sound: the counts
come from jax's own monitoring events and from the compiled module's text.
"""

from __future__ import annotations

import re
from typing import Dict


class CompileWatch:
    """Counts every executable jax builds or loads, and every persistent-cache
    hit and miss, from jax's monitoring events."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_):
        if event == self._COMPILE:
            self.compiles += 1
            self.compile_s += seconds

    def _on_event(self, event: str, **_):
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "hits": self.hits, "misses": self.misses}

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}


_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


#: where an instruction's text starts: ``  %name = `` or ``  ROOT %name = ``
_INSTRUCTION = re.compile(r"^[ \t]*(?:ROOT )?%?([\w.\-]+) = ", re.M)
_OPERANDS = re.compile(
    r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}")


def hlo_report(hlo: str) -> Dict:
    """From the compiled (per-device) module's text: the Mosaic custom calls
    by instruction name with the jax op that made each (``mosaic``) and the
    shapes the kernel is handed (``operands``: ``[dtype, dims]`` of each
    operand, from the call's ``operand_layout_constraints``), and the
    collectives by kind (an async pair counts once, at its ``-start``; the TPU
    compiler emits a reduce-scatter as a fusion that
    ``calls=%all-reduce-scatter``, counted as one).  An instruction's text
    runs to the next instruction's: a kernel's ``kernel_metadata`` holds
    newlines, and the call's ``op_name`` stands after them."""
    mosaic, operands = {}, {}
    starts = list(_INSTRUCTION.finditer(hlo))
    for at, following in zip(starts, starts[1:] + [None]):
        text = hlo[at.start():following.start() if following else len(hlo)]
        head = text.split("\n", 1)[0]
        if "tpu_custom_call" not in head or " custom-call(" not in head:
            continue
        op_name = re.search(r'op_name="([^"]*)"', text)
        mosaic[at.group(1)] = op_name.group(1) if op_name else ""
        handed = _OPERANDS.search(head)
        operands[at.group(1)] = [
            [dtype, [int(n) for n in dims.split(",") if n]]
            for dtype, dims in re.findall(r"([a-z]\w*)\[([\d,]*)\]",
                                          handed.group(1))] if handed else []
    counts = {kind: len(re.findall(rf" {kind}(?:-start)?\(", hlo))
              for kind in _COLLECTIVES}
    counts["reduce-scatter"] += len(re.findall(
        r" fusion\([^\n]*calls=%?all-reduce-scatter", hlo))
    return {"mosaic": mosaic, "operands": operands, "collectives": counts}
