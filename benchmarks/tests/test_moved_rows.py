"""``step.moe_moved_rows`` (PR 38): the reader of the step counter
``moe_moved`` in the ``StepProfiler`` rows, on made-up records (None on the
rows of a program that lacks the counter: every parent of PR 38, and a model
that holds every expert), held to the program's gauge on one row, and in a
rehearsal of the cell whose expert layers hold a share."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.lib import spec
from benchmarks.tests.test_device_rows import _new_rows, _record

PAIRS = 2 * 8192 * 8  # sdar-ep8-s8192: noised and clean copy, 8 a token


def _with_moved(rows, moved_of_layer):
    for i, row in enumerate(rows):
        row["moe_moved"] = [[moved_of_layer(i, layer)] for layer in range(6)]
    return rows


def test_the_reader_finds_nothing_without_the_counter():
    reader = spec.load_module("layer_metrics", "step.moe_moved_rows")
    # PR 36's rows: moe_rows and no moe_moved
    for rows in (_new_rows(27, lambda i, layer: 16384), []):
        run = _record("sdar-ep8-s8192", rows)
        assert reader.read(run) is None
        assert not reader.describe(run)


def test_the_mean_and_the_share_of_each_amount():
    window = PAIRS // 8
    # layer 5 of every ninth step moves every pair; layer l walks l % 3 + 1
    # windows
    rows = _with_moved(
        _new_rows(27, lambda i, layer: 4096),
        lambda i, layer: window * (8 if (layer == 5 and i % 9 == 0)
                                   else layer % 3 + 1))
    reader = spec.load_module("layer_metrics", "step.moe_moved_rows")
    run = _record("sdar-ep8-s8192", rows)
    moved = np.asarray([r["moe_moved"] for r in rows])
    assert reader.read(run) == pytest.approx(moved.mean())
    note = reader.describe(run)
    assert note["pairs_a_layer"] == PAIRS and note["rows"] == 27
    assert note["moved_every_pair"] == pytest.approx(3 / (27 * 6))
    shares = note["share_of_layer_steps_by_rows_moved"]
    assert list(shares) == [str(window * n) for n in (1, 2, 3, 8)]
    assert sum(shares.values()) == pytest.approx(1.0)
    assert shares[str(PAIRS)] == note["moved_every_pair"]
    assert note["per_layer_mean"][0] == window
    # never under the rows that met a held expert
    held = spec.load_module("layer_metrics", "step.moe_held_rows")
    assert reader.read(run) >= held.read(run)


def test_the_reader_the_row_and_the_programs_gauge_are_one_number():
    """``ray_tpu_train_moe_moved_rows`` and ``step.moe_moved_rows`` read one
    row's ``moe_moved`` alike, and the row's key is the counter's name in
    the program's registry."""
    from ray_tpu.train.metrics import COUNTER_GAUGES, moe_moved_rows
    from ray_tpu.util.tracing import STEP_COUNTER_REGISTRY

    assert "moe_moved" in STEP_COUNTER_REGISTRY
    assert COUNTER_GAUGES["moe_moved"][1] is moe_moved_rows
    rows = _with_moved(_new_rows(4, lambda i, layer: 4096),
                       lambda i, layer: PAIRS // (8 if layer < 4 else 2))[:1]
    reader = spec.load_module("layer_metrics", "step.moe_moved_rows")
    got = reader.read(_record("sdar-ep8-s8192", rows))
    assert got == pytest.approx((4 * PAIRS // 8 + 2 * PAIRS // 2) / 6)
    assert got == pytest.approx(moe_moved_rows(rows[0]["moe_moved"]))


def test_a_rehearsal_prints_the_count_where_a_share_is_held():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=spec.ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    values = {}
    for cell in ("sdar-ep8-s8192", "olmoe-s4096"):
        done = subprocess.run(
            [sys.executable, os.path.join(spec.ROOT, "benchmarks", "run.py"),
             "--workload", cell, "--seed", "3", "--seconds", "2", "--trace",
             "1", "--rehearse"], cwd=spec.ROOT, env=env, capture_output=True,
            text=True, timeout=600)
        assert done.returncode == 0, done.stderr[-3000:]
        values[cell] = json.loads(
            done.stdout.strip().splitlines()[-1])["metrics"]
    moved = values["sdar-ep8-s8192"]["step.moe_moved_rows"]
    assert moved["unit"] == "count"
    assert moved["value"] >= values["sdar-ep8-s8192"][
        "step.moe_held_rows"]["value"]
    # every expert held: every pair moves, nothing to count
    assert "step.moe_moved_rows" not in values["olmoe-s4096"]
