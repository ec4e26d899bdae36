"""Profiler overhead bench: StepProfiler must be ~free on a real step.

Runs the same jitted GPT-2 train step twice — bare, then with an active
StepProfiler closing a step window per iteration (spans enabled, i.e. the
worst configuration) — and compares median step times.  The acceptance
gate is <= 2% overhead: the profiler is always-on by default
(RunConfig.profile=True), so it must never show up in the step time it
measures.  Also checks the attribution invariant on the profiled run:
every row's buckets sum to its wall exactly.

A second A/B phase gates the flight recorder the same way: profiled steps
with the span tap installed (every span also lands in the black-box ring)
vs. tap removed, gate <= 1% — the recorder is always-on, so its cost must
stay in the noise even at full span volume.

A third A/B phase gates the device-telemetry plane: steps dispatched
through the instrumented-jit compile tap (per-call abstract-signature
computation against a warm compile cache) plus one transfer-ledger write
per step, vs. the bare jitted step.  Gate <= 1% — the tap wraps every
step function, so its steady-state (zero-compile) cost must stay in the
noise.

Writes BENCH_PROFILER.json next to the repo root and exits nonzero when
any gate fails.

  python scripts/bench_profiler.py                 # tiny config, CPU-ok
  python scripts/bench_profiler.py --config small --steps 40
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_PROFILER.json")


def _interleaved_times(step, params, opt_state, tokens, targets, n, prof):
    """Alternate bare and profiled steps so clock drift / thermal ramp /
    background load lands on both sets equally — a sequential A-then-B
    layout reads environment drift as profiler overhead."""
    from ray_tpu.train import profiler as train_profiler

    bare, profiled = [], []
    for i in range(2 * n):
        with_prof = i % 2 == 1
        if with_prof:
            train_profiler.activate(prof)
        try:
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, tokens, targets)
            float(loss)  # device sync
            if with_prof:
                prof.record("data_wait", time.time() - 1e-4, time.time())
                prof.step_boundary()
            (profiled if with_prof else bare).append(time.perf_counter() - t0)
        finally:
            if with_prof:
                train_profiler.activate(None)
    return bare, profiled, params, opt_state


def _recorder_times(step, params, opt_state, tokens, targets, n, prof):
    """Recorder A/B: every iteration runs profiled with spans on (the
    recorder's cost is the per-span tap, so spans must flow in BOTH arms);
    odd iterations have the ring tap installed, even ones don't.  Same
    interleaving rationale as above."""
    from ray_tpu.train import profiler as train_profiler
    from ray_tpu.util import flight_recorder, tracing

    rec = flight_recorder.FlightRecorder()
    off, on = [], []
    try:
        for i in range(2 * n):
            with_rec = i % 2 == 1
            tracing.set_span_tap(rec.tap_span if with_rec else None)
            train_profiler.activate(prof)
            try:
                t0 = time.perf_counter()
                params, opt_state, loss = step(params, opt_state, tokens,
                                               targets)
                float(loss)  # device sync
                prof.record("data_wait", time.time() - 1e-4, time.time())
                prof.step_boundary()
                (on if with_rec else off).append(time.perf_counter() - t0)
            finally:
                train_profiler.activate(None)
    finally:
        tracing.set_span_tap(None)
    return off, on, rec.events_recorded()


def _telemetry_times(step_tel, params, opt_state, tokens, targets, n):
    """Device-telemetry A/B: both arms execute the SAME compiled
    executable (two independent XLA compilations of one function can
    differ by more than the gate, which would read as tap overhead);
    odd iterations go through the ``TrainStep`` wrapper on top of it
    (dispatch annotation, compile label, profiler counter — zero compiles
    in steady state) and ledger one transfer, even iterations call the
    jitted function under it directly.  Same interleaving rationale as
    above."""
    from ray_tpu.util import device_telemetry

    compiled = step_tel._jitted
    bare, telem = [], []
    nbytes = int(tokens.size) * 4
    for i in range(2 * n):
        with_tel = i % 2 == 1
        t0 = time.perf_counter()
        if with_tel:
            params, opt_state, loss = step_tel(params, opt_state, tokens,
                                               targets)
            device_telemetry.record_transfer("h2d", nbytes, src="bench")
        else:
            params, opt_state, loss = compiled(params, opt_state, tokens,
                                               targets)
        float(loss)  # device sync
        (telem if with_tel else bare).append(time.perf_counter() - t0)
    return bare, telem, params, opt_state


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", choices=("tiny", "small"), default="tiny")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--gate-pct", type=float, default=2.0)
    ap.add_argument("--recorder-gate-pct", type=float, default=1.0)
    ap.add_argument("--telemetry-gate-pct", type=float, default=1.0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.train_state import jit_train_step
    from ray_tpu.train.profiler import StepProfiler
    from ray_tpu.util import device_telemetry, tracing

    config = (gpt2.GPTConfig.tiny() if args.config == "tiny"
              else gpt2.GPTConfig.small())
    B, S = args.batch, config.seq_len
    opt = gpt2.make_optimizer()
    params = gpt2.init_params(config, jax.random.key(0))
    opt_state = opt.init(params)
    fn = gpt2.make_train_step(config, opt)
    step = jax.jit(fn, donate_argnums=(0, 1))
    step_tel = jit_train_step(fn)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, config.vocab_size, (B, S + 1), dtype=np.int64)
    t = jnp.asarray(toks, jnp.int32)
    tokens, targets = t[:, :-1], t[:, 1:]

    # Compile + warm both dispatch paths outside the measured window.
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    params, opt_state, loss = step_tel(params, opt_state, tokens, targets)
    float(loss)

    prof = StepProfiler(run_name="bench", rank=0,
                        flops_per_step=gpt2.flops_per_token(config) * B * S,
                        tokens_per_step=B * S, peak_flops=197e12)
    tracing.clear_spans()
    tracing.enable_tracing()  # worst case: span emission on every boundary
    try:
        bare, profiled, params, opt_state = _interleaved_times(
            step, params, opt_state, tokens, targets, args.steps, prof)
        # Telemetry phase runs before the recorder phase: the recorder
        # loop donates params/opt_state without returning them.
        tel_off, tel_on, params, opt_state = _telemetry_times(
            step_tel, params, opt_state, tokens, targets, args.steps)
        rec_off, rec_on, ring_events = _recorder_times(
            step, params, opt_state, tokens, targets, args.steps, prof)
    finally:
        tracing.disable_tracing()
        tracing.clear_spans()
        device_telemetry.reset()

    med_bare = statistics.median(bare)
    med_prof = statistics.median(profiled)
    overhead_pct = (med_prof - med_bare) / med_bare * 100.0
    med_rec_off = statistics.median(rec_off)
    med_rec_on = statistics.median(rec_on)
    recorder_overhead_pct = (med_rec_on - med_rec_off) / med_rec_off * 100.0
    med_tel_off = statistics.median(tel_off)
    med_tel_on = statistics.median(tel_on)
    device_telemetry_overhead_pct = \
        (med_tel_on - med_tel_off) / med_tel_off * 100.0

    # Attribution invariant: buckets + compute == wall on every row.
    rows = list(prof.history)
    max_err = max((abs(sum(r[b] for b in
                           ("data_wait", "h2d", "collective", "ckpt_block",
                            "compute")) - r["wall"]) / r["wall"])
                  for r in rows)

    result = {
        "bench": "profiler_overhead",
        "config": args.config,
        "batch": B,
        "seq_len": S,
        "steps": args.steps,
        "backend": jax.default_backend(),
        "median_step_ms_bare": round(med_bare * 1e3, 4),
        "median_step_ms_profiled": round(med_prof * 1e3, 4),
        "overhead_pct": round(overhead_pct, 3),
        "gate_pct": args.gate_pct,
        "bucket_sum_max_rel_err": max_err,
        "profiled_rows": len(rows),
        "median_step_ms_recorder_off": round(med_rec_off * 1e3, 4),
        "median_step_ms_recorder_on": round(med_rec_on * 1e3, 4),
        "recorder_overhead_pct": round(recorder_overhead_pct, 3),
        "recorder_gate_pct": args.recorder_gate_pct,
        "recorder_ring_events": ring_events,
        "median_step_ms_telemetry_off": round(med_tel_off * 1e3, 4),
        "median_step_ms_telemetry_on": round(med_tel_on * 1e3, 4),
        "device_telemetry_overhead_pct": round(
            device_telemetry_overhead_pct, 3),
        "device_telemetry_gate_pct": args.telemetry_gate_pct,
        "passed": (overhead_pct <= args.gate_pct and max_err < 1e-9
                   and recorder_overhead_pct <= args.recorder_gate_pct
                   and device_telemetry_overhead_pct
                   <= args.telemetry_gate_pct),
    }
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result, indent=2), flush=True)
    if not result["passed"]:
        print(f"FAIL: overhead {overhead_pct:.2f}% > gate {args.gate_pct}%, "
              f"recorder overhead {recorder_overhead_pct:.2f}% > gate "
              f"{args.recorder_gate_pct}%, telemetry overhead "
              f"{device_telemetry_overhead_pct:.2f}% > gate "
              f"{args.telemetry_gate_pct}%, or attribution drift "
              f"{max_err:.2e}", file=sys.stderr)
        return 1
    print(f"OK: profiler overhead {overhead_pct:+.2f}% "
          f"(gate {args.gate_pct}%), recorder overhead "
          f"{recorder_overhead_pct:+.2f}% (gate {args.recorder_gate_pct}%), "
          f"telemetry overhead {device_telemetry_overhead_pct:+.2f}% "
          f"(gate {args.telemetry_gate_pct}%)",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
