"""The per-layer metrics that read the program's own names (PR 23), on traces
recorded on the chip with their ``TrainStep.anatomy()`` beside them
(``benchmarks/testdata/tiny-*-named.*``: the tiny presets through ``run.py
--rehearse --trace 1 --keep-trace`` on a TPU v5e, the anatomy asked of the
same process afterwards and cut to the instructions the trace holds).

Pinned values are the readers' own first results on these files, in
nanoseconds a step; what is checked by construction is that the groups add up
to the chip's busy time, which ``trace_reduce.busy_seconds`` counts another
way (a union of intervals, not a sum of self times)."""

import json
import os
import sys
import types

import pytest

from benchmarks.lib import anatomy, spec, trace_reduce as tr
from benchmarks.lib.record import RunRecord

STEP_READERS = ("step.forward_ms", "step.backward_ms", "step.recompute_ms",
                "step.optimizer_ms", "step.attn_ms", "step.mlp_ms",
                "step.lm_head_ms", "step.unscoped_ms")
ROW_READERS = ("trainer.dispatch_span_ms", "trainer.report_span_ms",
               "ingest.h2d_bytes")
PINNED = {  # ns a step
    "tiny-llama-named": {
        "step.forward_ms": 124606, "step.backward_ms": 188309,
        "step.recompute_ms": 68190, "step.optimizer_ms": 36011,
        "step.attn_ms": 171391, "step.mlp_ms": 107117,
        "step.lm_head_ms": 54436, "step.unscoped_ms": 15235,
        "kernels.splash_ms": 83943, "busy": 432417},
    "tiny-gpt2-named": {  # attn_outside: no splash in the recomputation
        "step.forward_ms": 178810, "step.backward_ms": 211635,
        "step.recompute_ms": 10661, "step.optimizer_ms": 17000,
        "step.attn_ms": 195515, "step.mlp_ms": 68776,
        "step.lm_head_ms": 94050, "step.unscoped_ms": 5419,
        "kernels.splash_ms": 116328, "busy": 423525},
}


def read(metric, run):
    return spec.load_module("layer_metrics", metric).read(run)


def recorded(name):
    base = os.path.join(spec.BENCH_DIR, "testdata", name)
    trace = tr.load(base + ".xplane.pb.gz")
    with open(base + ".anatomy.json") as f:
        held = json.load(f)
    run = RunRecord(
        cell={}, chips=1, peaks=None, tokens_per_step=2048,
        flops_per_step=1.0, seq_len=256, attention_calls=(), trace=trace,
        steady=tr.steady_window(trace.first.modules,
                                tr.step_module(trace.first.modules)),
        hlo={"mosaic": held["mosaic"], "collectives": {}})
    run.anatomy = {k: tuple(v) for k, v in held["anatomy"].items()}
    return run, held


@pytest.fixture(scope="module", params=sorted(PINNED))
def chip_run(request):
    return (request.param,) + recorded(request.param)


@pytest.mark.parametrize("metric", STEP_READERS + ("kernels.splash_ms",))
def test_reader_on_a_recorded_chip_trace(chip_run, metric):
    name, run, _ = chip_run
    assert round(read(metric, run) * 1e6) == PINNED[name][metric]


def test_phases_and_the_phaseless_rest_add_up_to_busy_time(chip_run):
    name, run, _ = chip_run
    lo, hi, steps, _ = run.steady
    busy_ns = tr.busy_seconds(run.trace.first.ops, lo, hi) / steps * 1e9
    assert round(busy_ns) == PINNED[name]["busy"]
    phases = sum(read(m, run) for m in STEP_READERS[:4])
    rest = anatomy.phase_ms(run, None)
    assert (phases + rest) * 1e6 == pytest.approx(busy_ns, rel=1e-5)
    # every instruction the trace holds is in the anatomy, so the nameless
    # rest is what the program left unnamed, not what the map lacks
    assert {e.name for e in run.trace.first.ops} <= set(run.anatomy)
    assert read("step.unscoped_ms", run) <= rest


def test_parts_lie_inside_the_step_and_splash_inside_attn(chip_run):
    _, run, _ = chip_run
    parts = sum(read(m, run) for m in STEP_READERS[4:7])
    assert parts < 1e3 * tr.median(run.steady[3])
    assert read("kernels.splash_ms", run) <= read("step.attn_ms", run)
    assert read("kernels.splash_ms", run) <= anatomy.part_ms(run,
                                                             "attn_kernel")


def test_report_keeps_the_breakdown_and_the_nameless(chip_run):
    _, run, _ = chip_run
    note = spec.load_module("layer_metrics", "step.unscoped_ms").describe(run)
    table = note["ms_by_phase_and_part"]
    assert list(table.values()) == sorted(table.values(), reverse=True)
    assert sum(table.values()) == pytest.approx(
        anatomy.ms_per_step(run, lambda key: True))
    assert {"update/optimizer", "forward/lm_head", "backward/mlp"} <= set(
        table)
    longest = note["longest_nameless"]
    assert longest and all(run.anatomy[row[0]] == (None, None)
                           for row in longest)
    assert sum(row[2] for row in longest) <= table["-/-"] * (1 + 1e-9)


def test_the_recorded_process_compiled_the_step_once(chip_run):
    _, _, held = chip_run
    steps = [r for r in held["compile_records"] if r["label"] == "train_step"]
    assert [r["trigger"] for r in steps] == ["first_compile"]
    # (anatomy() found the first call's executable still in memory: no
    # compile of its own; the unlabelled ones are the benchmark's programs)
    assert {"init_params", "init_opt_state", "unlabelled"} <= {
        r["label"] for r in held["compile_records"]}
    assert len(held["first_calls"]) == 1
    assert held["first_calls"][0]["seconds"] >= steps[0]["compile_s"]


def test_program_spans_are_on_the_profilers_clock():
    """The rehearsal's trace holds the program's spans on three host thread
    lines, and on the clock of the benchmark's own: every ``train.dispatch``
    lies inside the ``bench.dispatch`` around the same call."""
    import gzip

    from jax.profiler import ProfileData

    path = os.path.join(spec.BENCH_DIR, "testdata",
                        "tiny-llama-named.xplane.pb.gz")
    with gzip.open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    lines = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events
                         if e.name.split(".")[0] in ("bench", "train",
                                                     "data")]
                if spans:
                    lines[i] = spans
    by_name = {i: {n for n, _, _ in spans} for i, spans in lines.items()}
    worker = [i for i, names in by_name.items() if "train.dispatch" in names]
    assert len(worker) == 1
    assert {"bench.dispatch", "bench.report", "train.report",
            "data.prefetch"} <= by_name[worker[0]]
    assert any("data.pump" in names for i, names in by_name.items()
               if i != worker[0])
    assert any("train.result_drain" in names for i, names in by_name.items()
               if i != worker[0])
    outer = [(a, b) for n, a, b in lines[worker[0]] if n == "bench.dispatch"]
    inner = [(a, b) for n, a, b in lines[worker[0]] if n == "train.dispatch"]
    assert len(inner) == len(outer) == 8
    for (a, b), (lo, hi) in zip(sorted(inner), sorted(outer)):
        assert lo <= a and b <= hi
    # trace_reduce.load still keeps the benchmark's own spans only
    assert {e.name.split(".")[0] for e in tr.load(path).host_spans} \
        == {"bench"}


# ------------------------------------------- a program without the names
def test_readers_give_nothing_on_a_program_without_the_registry(
        monkeypatch):
    """The parent of PR 23: no anatomy, no counters in the rows, no first
    call kept.  Every new reader returns None and raises nothing."""
    run, _ = recorded("tiny-llama-named")
    del run.anatomy
    monkeypatch.setitem(sys.modules, "ray_tpu.util.device_telemetry",
                        types.ModuleType("device_telemetry"))
    run.profiler_rows = [{"step": 0, "wall": 0.3, "compute": 0.3,
                          "data_wait": 0.0, "h2d": 0.0, "collective": 0.0,
                          "ckpt_block": 0.0}]
    for metric in STEP_READERS + ROW_READERS + ("step.first_call_s",):
        reader = spec.load_module("layer_metrics", metric)
        assert reader.read(run) is None, metric
        if hasattr(reader, "describe"):
            assert not reader.describe(run), metric
    monkeypatch.delitem(sys.modules, "ray_tpu.util.device_telemetry")
    run2, _ = recorded("tiny-llama-named")
    del run2.anatomy
    assert read("step.forward_ms", run2) is None
    assert read("step.first_call_s", run2) is None


def test_anatomy_is_asked_of_the_program_once(monkeypatch):
    asked = []

    class Step:
        def anatomy(self):
            asked.append(1)
            return {"fusion.1": ["forward", "mlp"]}

    telemetry = types.ModuleType("device_telemetry")
    telemetry.program = {"train_step": Step()}.get
    telemetry.first_calls = lambda label: [
        {"label": label, "ts": 1.0, "seconds": 17.1},
        {"label": label, "ts": 9.0, "seconds": 0.5}]
    monkeypatch.setitem(sys.modules, "ray_tpu.util.device_telemetry",
                        telemetry)
    run, _ = recorded("tiny-llama-named")
    del run.anatomy
    for metric in STEP_READERS:
        assert read(metric, run) is not None
    assert asked == [1]
    assert run.anatomy == {"fusion.1": ("forward", "mlp")}
    # an instruction the anatomy does not know is nameless
    assert read("step.unscoped_ms", run) == pytest.approx(
        anatomy.ms_per_step(run, lambda key: True)
        - anatomy.part_ms(run, "mlp"))
    assert read("step.first_call_s", run) == 17.1


# ------------------------------------------------- the row readers
def test_row_readers_on_profiler_rows():
    run, _ = recorded("tiny-llama-named")
    base = {"wall": 0.265, "compute": 0.263, "data_wait": 0.0, "h2d": 0.002,
            "collective": 0.0, "ckpt_block": 0.0, "compiles": 0,
            "compile_s": 0}
    run.profiler_rows = [
        dict(base, step=i, dispatch=d, report=r, h2d_bytes=65536)
        for i, (d, r) in enumerate([(0.0008, 0.00009), (0.0009, 0.00010),
                                    (0.0020, 0.00041), (0.0007, 0.00008)])]
    run.tokens_per_step = 8192
    assert read("trainer.dispatch_span_ms", run) == pytest.approx(1.1)
    assert read("trainer.report_span_ms", run) == pytest.approx(0.17)
    assert read("ingest.h2d_bytes", run) == 64.0
    note = spec.load_module("layer_metrics",
                            "trainer.report_span_ms").describe(run)
    # the mean a hiccup moved, and the hiccup named
    assert note["median_ms"] == pytest.approx(0.095)
    assert (note["longest_ms"], note["longest_at_row"]) \
        == (pytest.approx(0.41), 2)
    note = spec.load_module("layer_metrics", "ingest.h2d_bytes").describe(run)
    assert note == {"distinct_bytes_per_step": [65536],
                    "bytes_per_token": [8.0]}
