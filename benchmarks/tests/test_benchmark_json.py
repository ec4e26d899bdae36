"""``BENCHMARK.json`` against its contract, and against the files it names."""

import json
import os
import re

import pytest

from benchmarks.lib import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
#: ``reduced`` may never name a width: a hidden, intermediate, latent, state
#: or projection size, a head size, an expansion factor, the experts a token
#: (``vocab_size`` is a cut the ``model-configs`` guide allows)
WIDTH = re.compile(r"(_dim|_rank|hidden_size|intermediate_size|state_size"
                   r"|n_embd|n_inner|n_head|expand|num_experts_per_tok)$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert bench["paths"] == ["benchmarks"]
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    seconds = bench["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    # A full check with all 24 cells has to fit into 43200 s.
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("benchmarks/")
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        held = json.load(open(os.path.join(spec.ROOT, c["file"])))
        assert held["source"] == c["source"]
        assert set(c["reduced"]) == set(held["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not WIDTH.search(key)
        assert held["layout"]["chips"] in (1, 4)
        # limits of its own for lib/correct.py come with their readings
        if "check" in held:
            assert set(held["check"]) <= {"loss_tol", "seed_grad_tol"}
            assert all(0 < v < 0.5 for v in held["check"].values())
            assert held["check_why"]
        spec.load_module("models", held["family"])
        spec.load_module("reference", held["family"])


def test_workloads(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        cell = spec.load_cell(bench, w["name"])
        assert cell["config_file"]["layout"]["chips"] == w["chips"]
        spec.load_module("kinds", cell["traffic_file"]["kind"])


def test_metrics(bench):
    end = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in end and end["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in end
        reader = spec.load_module("layer_metrics", m["name"])
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
        assert callable(reader.read)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells


def test_every_reader_is_listed():
    """A reader file nobody lists is dead; a later PR adds both together."""
    listed = {m["name"] for m in spec.load_benchmark()["per_layer"]}
    on_disk = {f[:-3] for f in os.listdir(
        os.path.join(spec.BENCH_DIR, "layer_metrics")) if f.endswith(".py")}
    assert on_disk == listed
