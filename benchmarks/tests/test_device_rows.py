"""The readers of the ``StepProfiler`` rows' device side (PR 36):
``step.done_period_ms``, ``step.done_period_spread``,
``trainer.starved_dispatches``, ``step.moe_held_rows``,
``step.moe_load_max`` and ``kernels.gmm_held_roofline``, on made-up records
(a reader has to return ``None`` on the rows of a program that lacks the
keys, which is every parent of PR 36) and in a rehearsal of the two cells
with experts."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks.lib import spec
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.record import RunRecord

NEW = ("step.done_period_ms", "step.done_period_spread",
       "trainer.starved_dispatches", "step.moe_held_rows",
       "step.moe_load_max", "kernels.gmm_held_roofline")


def _record(cell_name, rows, **more):
    cell = spec.load_cell(spec.load_benchmark(), cell_name)
    return RunRecord(cell=cell, chips=1, peaks=peaks_for("TPU v5 lite"),
                     tokens_per_step=8192, flops_per_step=1.0,
                     seq_len=8192, attention_calls=(), steps=len(rows),
                     profiler_rows=rows, **more)


def _old_rows(n):
    return [{"step": i, "wall": 0.1, "compute": 0.1, "data_wait": 0.0,
             "h2d": 0.0, "collective": 0.0, "ckpt_block": 0.0,
             "dispatch": 1e-3, "report": 1e-4, "h2d_bytes": 65536,
             "compiles": 0, "compile_s": 0.0} for i in range(n)]


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_the_parents_rows(name):
    reader = spec.load_module("layer_metrics", name)
    for rows in (_old_rows(30), []):
        run = _record("sdar-ep8-s8192", rows)
        assert reader.read(run) is None
        assert not reader.describe(run)


def _new_rows(n, held_of_layer, period=0.75):
    """Rows as PR 36 leaves them: a device-paced run, the queue eight deep,
    but for the rows the harness empties it before (0, 3, 11)."""
    rows = _old_rows(n)
    for i, row in enumerate(rows):
        late = period + (0.5 if i in (0, 3, 11) else 0.0) + 1e-4 * (i % 3)
        row.update(done=100.0 + period * i, in_flight=8,
                   device_period=None if i == 0 else late,
                   moe_rows=[[[held_of_layer(i, layer) // 16] * 16]
                             for layer in range(6)])
    for i in (0, 3, 11)[:3 if n > 11 else 2]:
        rows[i]["in_flight"] = 0
    return rows


def test_the_period_readers_leave_out_the_harnesss_own_stalls():
    run = _record("sdar-ep8-s8192", _new_rows(27, lambda i, layer: 16384))
    period = spec.load_module("layer_metrics", "step.done_period_ms")
    spread = spec.load_module("layer_metrics", "step.done_period_spread")
    assert period.read(run) == pytest.approx(750.1, abs=0.11)
    assert spread.read(run) == pytest.approx(100 * 2e-4 / 0.7501, rel=0.01)
    note = period.describe(run)
    assert note["rows_left_out"] == [0] + list(range(2, 13))
    assert note["rows"] == 27 - 12
    assert note["correlation_with_held_rows"] is None  # the rows are flat
    starved = spec.load_module("layer_metrics", "trainer.starved_dispatches")
    assert starved.read(run) == 0
    run.profiler_rows[20]["in_flight"] = 0
    assert starved.read(run) == 1
    assert starved.describe(run)["starved_rows"] == [20]


def test_a_window_that_ends_with_the_trace_reads_the_traced_rows():
    """The four-chip cell: stopping the trace outlasts the window, so the
    window is rows 0-10 and one untraced row is left."""
    run = _record("mistral7b-fsdp4-s4096",
                  _new_rows(11, lambda i, layer: 16384, period=0.6166))
    period = spec.load_module("layer_metrics", "step.done_period_ms")
    note = period.describe(run)
    assert note["rows_left_out"] == [0, 1, 2, 3] and note["rows"] == 7
    assert period.read(run) == pytest.approx(616.7, abs=0.11)


def test_the_rows_readers_and_the_roofline_join():
    # layer l of window step i holds 16 x (512 + 16 i + l) rows
    rows = _new_rows(27, lambda i, layer: 16 * (512 + 16 * i + layer))
    held = spec.load_module("layer_metrics", "step.moe_held_rows")
    load = spec.load_module("layer_metrics", "step.moe_load_max")
    run = _record("sdar-ep8-s8192", rows)
    mean = 16 * (512 + 16 * 13 + 2.5)
    assert held.read(run) == pytest.approx(mean)
    note = held.describe(run)
    assert note["pairs_a_layer"] == 2 * 8192 * 8
    assert note["per_layer"][0]["min"] == 16 * 512
    assert note["share_of_layer_steps_over"]["1/4"] == 0.0
    assert load.read(run) == pytest.approx(1.0)  # even groups

    # The trace's steady stretch: 7 whole steps before the traced
    # stretch's last dispatch (window step 10), so window steps 3..9.
    roofline = spec.load_module("layer_metrics", "kernels.gmm_held_roofline")
    assert roofline.read(run) is None  # no trace, nothing to join
    event = SimpleNamespace(name="gmm.1", start=1.0, dur=0.010)
    run.trace = SimpleNamespace(first=SimpleNamespace(ops=[event] * 7))
    run.steady = (0.0, 10.0, 7, [0.75] * 7)
    run.hlo = {"mosaic": ["gmm.1"]}
    note = roofline.describe(run)
    assert note["window_rows"] == list(range(3, 10))
    assert note["held_rows_a_layer"][0] == [16 * (512 + 16 * 3 + layer)
                                            for layer in range(6)]
    # 7 steps of 10 ms of kernel time against their own least time
    assert roofline.read(run) == pytest.approx(
        100 * note["least_ms_per_step"] / 10.0)
    assert 0 < roofline.read(run) < 100
    # the period reader puts its clock beside the trace's on the same steps
    period = spec.load_module("layer_metrics", "step.done_period_ms")
    assert period.describe(run)["same_steps_as_the_trace"] == pytest.approx(
        750.1, abs=0.11)


@pytest.mark.parametrize("cell", ["olmoe-s4096", "sdar-ep8-s8192"])
def test_a_rehearsal_prints_the_two_counts(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=spec.ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "3", "--seconds", "2", "--trace", "1",
         "--rehearse"], cwd=spec.ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    metrics = json.loads(done.stdout.strip().splitlines()[-1])["metrics"]
    assert metrics["trainer.starved_dispatches"]["unit"] == "count"
    assert metrics["step.moe_held_rows"]["value"] > 0
    assert {m["unit"] for m in metrics.values()} == {"count"}


def test_the_load_reader_and_the_programs_gauge_agree():
    """``ray_tpu_train_moe_load_max`` and ``step.moe_load_max`` are one
    quantity on one row: the same shards averaged, the same layers left
    out (a shard or a layer that sent the held experts nothing)."""
    from ray_tpu.train.metrics import moe_load_max

    moe_rows = [[[6, 2, 0, 0], [1, 1, 1, 1]],   # uneven, even
                [[0, 0, 0, 0], [4, 0, 0, 0]],   # a shard with no row
                [[0, 0, 0, 0], [0, 0, 0, 0]]]   # a layer with none
    rows = _old_rows(1)
    rows[0].update(done=1.0, in_flight=0, device_period=None,
                   moe_rows=moe_rows)
    load = spec.load_module("layer_metrics", "step.moe_load_max")
    got = load.read(_record("sdar-ep8-s8192", rows))
    assert got == pytest.approx(((3.0 + 1.0) / 2 + 4.0) / 2)
    assert got == pytest.approx(moe_load_max(moe_rows))
