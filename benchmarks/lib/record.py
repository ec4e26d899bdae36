"""What one run hands the per-layer metric readers
(``benchmarks/layer_metrics/<name>.py``, ``read(run)``).  A reader that finds
nothing to read returns None and the harness leaves the metric out."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from benchmarks.lib import trace_reduce


@dataclass
class RunRecord:
    cell: Dict                      # workload entry + config and traffic files
    chips: int
    peaks: Any                      # lib/peaks.py Peaks of one chip
    tokens_per_step: int
    flops_per_step: float           # lib/cost.py, the whole mesh
    seq_len: int
    attention_calls: Any            # lib/family.py AttentionCall kinds
    steps: int = 0                  # dispatched in the window
    window_s: float = 0.0           # fenced
    init_state_s: float = 0.0
    #: host-clock seconds of each window step's spans, by span name
    #: (next_batch, dispatch, report, fence)
    host_spans: Dict[str, List[float]] = field(default_factory=dict)
    #: StepProfiler rows of the window's steps (data_wait, h2d, ... seconds)
    profiler_rows: List[Dict] = field(default_factory=list)
    compile_setup: Dict = field(default_factory=dict)    # CompileWatch deltas
    compile_window: Dict = field(default_factory=dict)
    memory: List[Dict] = field(default_factory=list)     # per chip, after window
    #: traced runs only
    trace: Optional[Any] = None     # lib/trace_reduce.py Trace
    steady: Optional[Any] = None    # (lo, hi, steps, periods) on device 0
    step_module: Optional[str] = None
    hlo: Optional[Dict] = None      # lib/compile_watch.py hlo_report
    step_memory: Optional[Dict] = None  # compiled.memory_analysis() sizes

    @property
    def step_seconds(self) -> Optional[float]:
        """Median period between starts of the step program on the first
        chip, over the trace's steady stretch."""
        return trace_reduce.median(self.steady[3]) if self.steady else None

    def bucket_ms(self, bucket: str) -> Optional[float]:
        """Mean milliseconds a window step spent in a ``StepProfiler``
        bucket."""
        rows = self.profiler_rows
        return 1e3 * sum(r[bucket] for r in rows) / len(rows) if rows \
            else None

    def kernel_events(self, pattern: str) -> List[Any]:
        """The first chip's events, inside the trace's steady stretch, of the
        compiled step's Mosaic calls whose instruction name holds
        ``pattern`` (the kernel's own name: ``splash_mha_fwd_residuals.15``).
        Empty without a trace or the step's compiled text."""
        if not (self.trace and self.steady and self.hlo):
            return []
        names = {n for n in self.hlo["mosaic"] if pattern in n}
        lo, hi = self.steady[:2]
        return [e for e in self.trace.first.ops
                if lo <= e.start < hi and e.name in names]
