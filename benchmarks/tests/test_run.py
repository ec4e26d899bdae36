"""The whole command, as the driver starts it, on the CPU: it refuses to
measure there, and ``--rehearse`` runs every layout at a tiny size and prints
counts only."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import spec

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(root, *flags, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=spec.ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *flags],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)


def tree(tmp_path, bench, program=True):
    """A checkout in ``tmp_path``: the benchmark's files copied, the program
    linked (or left out), and ``bench`` as its BENCHMARK.json."""
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    if program:
        os.symlink(os.path.join(spec.ROOT, "ray_tpu"), tmp_path / "ray_tpu")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def result_line(done):
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_refuses_to_measure_on_a_cpu():
    done = run(spec.ROOT, "--workload", "mistral7b-s1024", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert done.returncode != 0 and done.stdout == ""
    assert "needs a TPU" in done.stderr


def test_refuses_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under paths."""
    tree(tmp_path, spec.load_benchmark(), program=False)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mistral7b-s1024",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0 and done.stdout == ""
    assert "no ray_tpu package" in done.stderr


@pytest.mark.parametrize("cell, trace, devices", [
    ("mistral7b-s8192", 0, 1),
    ("gpt2xl-s1024", 1, 1),
    ("mistral7b-fsdp4-s4096", 1, 4),
])
def test_rehearsal_prints_counts_only(cell, trace, devices):
    line = result_line(run(spec.ROOT, "--workload", cell, "--seed", "3",
                           "--seconds", "2", "--trace", str(trace),
                           "--rehearse"))
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    assert line["metrics"]
    assert {m["unit"] for m in line["metrics"].values()} == {"count"}
    if trace:
        assert line["metrics"]["step.compiles_in_window"]["value"] == 0
    if devices == 4:
        assert line["metrics"]["collectives.count"]["value"] > 0


def test_additions_are_data(tmp_path):
    """A later PR adds a configuration, a traffic mix, a per-layer metric and
    a cell as new files and entries, and edits no file that is there."""
    bench = spec.load_benchmark()
    tree(tmp_path, bench)
    new = tmp_path / "benchmarks"
    config = spec.load_json(spec.BENCH_DIR, "configs", "tiny-llama.json")
    config.update(num_hidden_layers=1, source="none: a test's",
                  reduced={}, layout={"chips": 1, "mesh": {"data": 1}})
    (new / "configs" / "added.json").write_text(json.dumps(config))
    traffic = spec.load_json(spec.BENCH_DIR, "traffic",
                             "packed-s1024-b8.json")
    traffic.update(seq_len=128, seqs_per_chip=2, doc_len_median=40)
    (new / "traffic" / "added-mix.json").write_text(json.dumps(traffic))
    (new / "layer_metrics" / "added.steps.py").write_text(
        'LAYER, UNIT, SOURCE, MOVES = "trainer", "count", '
        '"program_counter", "tokens_per_s_per_chip"\n\n\n'
        "def read(run):\n    return run.steps\n")
    bench["configs"].append({"name": "added", "source": "none: a test's",
                             "file": "benchmarks/configs/added.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "added-cell", "config": "added",
                               "traffic": "added-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "added.steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "tokens_per_s_per_chip", "workloads": ["added-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    line = result_line(run(str(tmp_path), "--workload", "added-cell",
                           "--seed", "5", "--seconds", "1", "--trace", "1",
                           "--rehearse"))
    assert line["correct"] is True
    assert line["metrics"]["added.steps"]["value"] == line["attempted"] > 0
    assert "collectives.count" not in line["metrics"]
