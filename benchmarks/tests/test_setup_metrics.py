"""The nine per-layer metrics that read the set-up from the program's own
registry (``ray_tpu.util.device_telemetry``: first-call records, compile
records, ``setup_account()``) and the ``hand_over`` row counter: nothing to
read is None, a registry filled by hand reads back, and a rehearsal prints
the count among them and no time."""

import json
import sys
import types

import pytest

from benchmarks.lib import spec
from benchmarks.lib.record import RunRecord
from benchmarks.tests.test_run import result_line, run

READERS = ("step.trace_s", "step.lower_s", "step.cache_load_s",
           "step.cache_misses", "trainer.runtime_init_s",
           "trainer.fit_setup_s", "ingest.first_batch_s",
           "trainer.setup_unspanned_s", "trainer.hand_over_ms")
TELEMETRY = "ray_tpu.util.device_telemetry"


def record(**kwargs):
    return RunRecord(cell={}, chips=1, peaks=None, tokens_per_step=1,
                     flops_per_step=1.0, seq_len=1, attention_calls=(),
                     **kwargs)


@pytest.fixture
def by_hand(monkeypatch):
    """A registry filled by hand in the program's place."""
    account = {
        "start": 100.0, "start_from": "proc",
        "rows": [
            {"name": "runtime.init", "start": 103.0, "end": 103.5},
            {"name": "train.fit_setup", "start": 103.5, "end": 103.75},
            {"name": "train.init_params", "start": 104.0, "end": 106.0},
            {"name": "train.first_batch", "start": 106.0, "end": 106.125},
            {"name": "train.first_call", "start": 106.125, "end": 116.125}],
        "closed": {"ts": 117.0, "by": "device"},
        "gaps": [{"after": "process_start", "before": "runtime.init",
                  "seconds": 3.0}],
        "spanned_s": 12.875, "unspanned_s": 4.125, "to_first_step_s": 17.0}
    calls = [{"label": "train_step", "ts": 116.125, "seconds": 10.0,
              "trace_s": 4.0, "lower_s": 1.5, "compile_s": 3.0,
              "other_s": 1.5, "cache_load_s": 2.5, "cache": "hit"},
             {"label": "train_step", "ts": 300.0, "seconds": 9.0,
              "trace_s": 8.0}]
    compiles = [{"label": "init_params", "ts": 105.0, "cache": "miss"},
                {"label": "unlabelled", "ts": 105.5, "cache": None},
                {"label": "train_step", "ts": 116.0, "cache": "hit"},
                {"label": "unlabelled", "ts": 200.0, "cache": "miss"}]
    module = types.SimpleNamespace(
        setup_account=lambda: account,
        first_calls=lambda label=None: [c for c in calls
                                        if label in (None, c["label"])],
        compile_records=lambda label=None: compiles)
    monkeypatch.setitem(sys.modules, TELEMETRY, module)
    return module


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_none(name, monkeypatch):
    monkeypatch.delitem(sys.modules, TELEMETRY, raising=False)
    reader = spec.load_module("layer_metrics", name)
    assert reader.read(record()) is None
    if hasattr(reader, "describe"):
        assert reader.describe(record()) is None or name == "step.trace_s"
    entry = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}[name]
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert "workloads" not in entry and entry["better"] == "lower"


@pytest.mark.parametrize("name", READERS[:-1])
def test_the_parents_registry_reads_as_nothing(name, monkeypatch):
    """The parent has first-call records of three keys, compile records
    and no account: no time is read from it, and nothing raises."""
    monkeypatch.setitem(sys.modules, TELEMETRY, types.SimpleNamespace(
        first_calls=lambda label=None: [
            {"label": "train_step", "ts": 1.0, "seconds": 9.0}],
        compile_records=lambda label=None: [
            {"label": "train_step", "ts": 1.0, "cache": "miss"}]))
    reader = spec.load_module("layer_metrics", name)
    assert reader.read(record()) is None
    if hasattr(reader, "describe"):
        json.dumps(reader.describe(record()))


@pytest.mark.parametrize("name, value", [
    ("step.trace_s", 4.0), ("step.lower_s", 1.5), ("step.cache_load_s", 2.5),
    ("step.cache_misses", 1), ("trainer.runtime_init_s", 0.5),
    ("trainer.fit_setup_s", 0.25), ("ingest.first_batch_s", 0.125),
    ("trainer.setup_unspanned_s", 4.125)])
def test_a_registry_filled_by_hand_reads_back(name, value, by_hand):
    reader = spec.load_module("layer_metrics", name)
    assert reader.read(record()) == value
    if hasattr(reader, "describe"):
        json.dumps(reader.describe(record()))


def test_what_the_reports_hold(by_hand):
    note = spec.load_module("layer_metrics", "step.trace_s").describe(record())
    assert note["train_step"] == {
        "seconds": 10.0, "trace_s": 4.0, "lower_s": 1.5, "compile_s": 3.0,
        "other_s": 1.5, "cache_load_s": 2.5, "cache": "hit"}
    assert note["init_params"] is None
    note = spec.load_module("layer_metrics",
                            "trainer.setup_unspanned_s").describe(record())
    assert note["to_first_step_s"] == 17.0 and note["closed_by"] == "device"
    assert note["gaps"][0]["before"] == "runtime.init"
    assert ["train.first_call", 10.0] in note["rows_s"]
    # the set-up's compiles: what follows the first steady step is not
    note = spec.load_module("layer_metrics",
                            "step.cache_misses").describe(record())
    assert note == {"init_params:miss": 1, "unlabelled:None": 1,
                    "train_step:hit": 1}
    by_hand.setup_account = lambda: {"rows": [], "closed": None,
                                     "unspanned_s": None}  # still open
    assert spec.load_module("layer_metrics",
                            "step.cache_misses").read(record()) is None


def test_hand_over_reads_the_rows_counter():
    reader = spec.load_module("layer_metrics", "trainer.hand_over_ms")
    rows = [{"dispatch": 0.25, "hand_over": 0.001},
            {"dispatch": 0.25, "hand_over": 0.003}]
    assert reader.read(record(profiler_rows=rows)) == pytest.approx(2.0)
    assert reader.describe(record(profiler_rows=rows))["longest_ms"] \
        == pytest.approx(3.0)
    assert reader.read(record(profiler_rows=[{"dispatch": 0.25}])) is None


def test_a_rehearsal_prints_the_count_and_no_time():
    line = result_line(run(spec.ROOT, "--workload", "mistral7b-s1024",
                           "--seed", "1", "--seconds", "2", "--trace", "1",
                           "--rehearse"))
    assert line["correct"] is True
    assert line["metrics"]["step.cache_misses"]["unit"] == "count"
    assert isinstance(line["metrics"]["step.cache_misses"]["value"], int)
    assert not set(READERS) - {"step.cache_misses"} & set(line["metrics"])
    with open(f"{spec.ROOT}/chiprun_out/benchmarks/mistral7b-s1024.seed1."
              "trace1.rehearse.json") as f:
        notes = json.load(f)["metric_notes"]
    account = notes["trainer.setup_unspanned_s"]
    assert [name for name, _ in account["rows_s"]][:4] == [
        "runtime.init", "train.fit_setup", "train.init_params",
        "train.init_opt_state"]
    assert account["closed_by"] == "device"
    split = notes["step.trace_s"]["train_step"]
    assert split["trace_s"] + split["lower_s"] + split["compile_s"] \
        + split["other_s"] == pytest.approx(split["seconds"], abs=1e-4)
