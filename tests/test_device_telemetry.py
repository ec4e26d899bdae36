"""Device telemetry plane (docs/observability.md § Device telemetry).

Bottom-up:

* compile-trigger classification (first_compile / shape_change /
  sharding_change / donation_change / recompile) and the compile registry,
* the recompile-storm detector (threshold, window expiry, drain/re-arm)
  and the acceptance chaos path: a storm must leave a ring event, a
  postmortem dump, and a ``storm:xla.compile_storm`` marker on the fused
  Perfetto timeline, with the bundle embedding the device snapshot,
* HBM pool accounting (add/sub/peak/zero-floor, tree_nbytes, and the
  kv_blocks hook site inside BlockAllocator),
* the transfer ledger + windowed ``transfer_bw`` accessor and the
  ``device_put_batch`` h2d hook,
* the compile listener on REAL jitted functions (jax's monitoring events
  under a ``compile_label``); the ``TrainStep`` cases (exactly one
  first-compile, zero recompiles over the steps that follow) are in
  ``tests/test_step_names.py``,
* snapshot/bundle embedding, the ``device_telemetry_snapshot`` fault
  point absorption, collector rollup, the Perfetto "device" lane, and
  the serve accessor / reason-label satellites,
* ``scripts/check_bench_gates.py`` (schema pass on the real artifacts,
  injected violations fail).
"""

import json
import os
import time

import numpy as np
import pytest

from ray_tpu.util import device_telemetry as dt
from ray_tpu.util import flight_recorder, forensics, tracing, watchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _set_chaos(spec: str) -> None:
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu._private.fault_injection import reset_injector

    GLOBAL_CONFIG.testing_rpc_failure = spec
    reset_injector()


@pytest.fixture(autouse=True)
def clean_telemetry():
    dt.reset()
    yield
    dt.reset()


@pytest.fixture
def recorder_env(monkeypatch, tmp_path):
    """Isolated postmortem dir + fresh recorder/watchdog singletons (same
    shape as the test_forensics fixture)."""
    pm_dir = tmp_path / "postmortems"
    monkeypatch.setenv("RAY_TPU_POSTMORTEM_DIR", str(pm_dir))
    monkeypatch.setenv("RAY_TPU_POSTMORTEM_MIN_INTERVAL_S", "0")
    monkeypatch.setenv("RAY_TPU_HANG_WATCHDOG", "0")
    flight_recorder.reset_recorder()
    watchdog.reset_watchdog()
    yield pm_dir
    flight_recorder.reset_recorder()
    watchdog.reset_watchdog()
    tracing.disable_tracing()
    tracing.clear_spans()


# --------------------------------------------------------------------------
# Compile-trigger classification
# --------------------------------------------------------------------------
class TestTriggerClassification:
    def test_precedence_sequence(self):
        assert dt.record_compile("f", shapes=("a",), shardings=("s1",),
                                 donation=(0,)) == dt.TRIGGER_FIRST
        assert dt.record_compile("f", shapes=("b",), shardings=("s1",),
                                 donation=(0,)) == dt.TRIGGER_SHAPE
        assert dt.record_compile("f", shapes=("b",), shardings=("s2",),
                                 donation=(0,)) == dt.TRIGGER_SHARDING
        assert dt.record_compile("f", shapes=("b",), shardings=("s2",),
                                 donation=(0, 1)) == dt.TRIGGER_DONATION
        assert dt.record_compile("f", shapes=("b",), shardings=("s2",),
                                 donation=(0, 1)) == dt.TRIGGER_RECOMPILE

    def test_labels_classify_independently(self):
        dt.record_compile("f", shapes=("a",))
        assert dt.record_compile("g", shapes=("a",)) == dt.TRIGGER_FIRST

    def test_registry_tail_and_totals(self):
        dt.record_compile("f", shapes=("a",), trace_s=0.5, compile_s=1.0)
        dt.record_compile("f", shapes=("b",), trace_s=0.25, compile_s=0.25)
        dt.record_compile("g", shapes=("a",))
        rows = dt.compile_records("f")
        assert [r["trigger"] for r in rows] == [dt.TRIGGER_FIRST,
                                                dt.TRIGGER_SHAPE]
        assert all(r["label"] == "f" for r in rows)
        totals = dt.compile_totals()
        assert totals["compiles"] == 3
        assert totals["by_trigger"] == {dt.TRIGGER_FIRST: 2,
                                        dt.TRIGGER_SHAPE: 1}
        assert totals["compile_seconds"] == pytest.approx(2.0)

    def test_a_record_carries_its_phases_and_the_caches_answer(self):
        tracing.clear_spans()
        tracing.enable_tracing()
        try:
            dt.record_compile("f", shapes=("a",), trace_s=0.5, lower_s=0.25,
                              compile_s=1.0, cache="hit", cache_load_s=0.75,
                              saved_s=9.0, ts=100.0, start=98.0)
            dt.record_compile("f", shapes=("a",), trace_s=0.5, lower_s=0.25,
                              compile_s=1.0, ts=200.0)
            first, second = [s for s in tracing.exported_spans()
                             if s["name"] == "xla.compile"]
        finally:
            tracing.disable_tracing()
            tracing.clear_spans()
        row = dt.compile_records("f")[0]
        assert (row["trace_s"], row["lower_s"], row["compile_s"]) \
            == (0.5, 0.25, 1.0)
        assert (row["cache"], row["cache_load_s"], row["saved_s"]) \
            == ("hit", 0.75, 9.0)
        # the lowering counts: in the totals and in the span's extent
        assert dt.compile_totals()["compile_seconds"] == pytest.approx(3.5)
        assert (first["start"], first["end"]) == (98.0, 100.0)
        assert (second["start"], second["end"]) == (198.25, 200.0)
        assert first["attributes"]["cache"] == "hit"
        assert first["attributes"]["lower_s"] == 0.25

    def test_classify_trigger_is_read_only(self):
        dt.record_compile("f", shapes=("a",))
        # Peeking twice at the same changed signature must not update the
        # last-seen state.
        assert dt.classify_trigger("f", ("b",), None, ()) == dt.TRIGGER_SHAPE
        assert dt.classify_trigger("f", ("b",), None, ()) == dt.TRIGGER_SHAPE


# --------------------------------------------------------------------------
# Recompile-storm detector
# --------------------------------------------------------------------------
class TestStormDetector:
    def test_threshold_drain_and_rearm(self, monkeypatch):
        monkeypatch.setenv("RAY_TPU_COMPILE_STORM_THRESHOLD", "2")
        monkeypatch.setenv("RAY_TPU_COMPILE_STORM_WINDOW_S", "60")
        dt.record_compile("f", shapes=("a",), ts=1.0)  # first: not counted
        dt.record_compile("f", shapes=("b",), ts=2.0)
        assert dt.compile_totals()["storms"] == 0
        dt.record_compile("f", shapes=("a",), ts=3.0)
        assert dt.compile_totals()["storms"] == 1
        # Firing drained the window: one more recompile is below threshold,
        # the next one re-trips.
        dt.record_compile("f", shapes=("b",), ts=4.0)
        assert dt.compile_totals()["storms"] == 1
        dt.record_compile("f", shapes=("a",), ts=5.0)
        assert dt.compile_totals()["storms"] == 2

    def test_window_expiry(self, monkeypatch):
        monkeypatch.setenv("RAY_TPU_COMPILE_STORM_THRESHOLD", "2")
        monkeypatch.setenv("RAY_TPU_COMPILE_STORM_WINDOW_S", "60")
        dt.record_compile("f", shapes=("a",), ts=0.0)
        dt.record_compile("f", shapes=("b",), ts=1.0)
        # 100s later the first recompile has aged out of the window.
        dt.record_compile("f", shapes=("a",), ts=100.0)
        assert dt.compile_totals()["storms"] == 0

    def test_storm_chaos_postmortem_and_fused_timeline(self, recorder_env,
                                                       monkeypatch):
        """ISSUE acceptance: a recompile storm must leave (a) an ERROR
        ring event, (b) a postmortem dump whose fused Perfetto timeline
        carries the ``storm:xla.compile_storm`` marker, and (c) a bundle
        embedding the device-telemetry snapshot."""
        monkeypatch.setenv("RAY_TPU_COMPILE_STORM_THRESHOLD", "3")
        shapes = [("a",), ("b",)]
        for i in range(4):  # first compile + 3 shape-change recompiles
            dt.record_compile("storm_fn", shapes=shapes[i % 2])
        assert dt.compile_totals()["storms"] == 1

        rec = flight_recorder.get_recorder()
        assert rec is not None
        storm_rows = [r for r in rec.snapshot() if r["kind"] == "storm"]
        assert storm_rows and storm_rows[0]["name"] == "xla.compile_storm"
        assert storm_rows[0]["status"] == "ERROR"

        rows = [r for r in forensics.list_postmortems()
                if "compile_storm" in str(r.get("reason"))]
        assert rows, "storm did not trigger a postmortem dump"
        dump = forensics.load_postmortem(rows[0]["id"])
        assert dump["extra"]["recompiles"] >= 3

        bundle = forensics.build_bundle()
        snap = bundle["device_telemetry"]
        assert snap is not None
        assert snap["compiles"]["totals"]["storms"] == 1
        assert snap["compiles"]["totals"]["by_trigger"][dt.TRIGGER_SHAPE] == 3

        names = {e["name"] for e in forensics.bundle_chrome_trace(bundle)}
        assert "storm:xla.compile_storm" in names
        assert "dump:compile_storm" in names


# --------------------------------------------------------------------------
# HBM pool accounting
# --------------------------------------------------------------------------
class TestPools:
    def test_add_sub_peak_and_floor(self):
        dt.pool_add("p", 100)
        dt.pool_add("p", 50)
        dt.pool_sub("p", 120)
        pools = dt.pool_bytes()
        assert pools["p"] == {"bytes": 30.0, "peak": 150.0}
        # Release paths may double-run after a failure: floored at zero.
        dt.pool_sub("p", 1000)
        assert dt.pool_bytes()["p"]["bytes"] == 0.0
        assert dt.pool_bytes()["p"]["peak"] == 150.0
        assert dt.POOL_BYTES.get({"pool": "p"}) == 0.0
        assert dt.POOL_PEAK_BYTES.get({"pool": "p"}) == 150.0

    def test_pool_set_absolute(self):
        dt.pool_add("q", 10)
        dt.pool_set("q", 500)
        dt.pool_set("q", 200)
        assert dt.pool_bytes()["q"] == {"bytes": 200.0, "peak": 500.0}

    def test_tree_nbytes(self):
        tree = {"a": np.zeros((4, 4), np.float32),
                "b": [np.zeros(8, np.int64), "not-an-array"],
                "c": (np.zeros(0, np.float32),)}
        assert dt.tree_nbytes(tree) == 4 * 4 * 4 + 8 * 8
        assert dt.tree_nbytes("just a string") == 0

    def test_kv_blocks_hook_site(self):
        """BlockAllocator page mutations keep the kv_blocks pool balanced:
        append charges, free/trim release, COW charges the copy."""
        from ray_tpu.serve.llm.blocks import BlockAllocator

        entry = np.zeros(16, np.float32)  # 64 bytes
        alloc = BlockAllocator(num_blocks=4, block_size=4)
        (b,) = alloc.allocate(1)
        for _ in range(3):
            alloc.append_entry(b, entry)
        assert dt.pool_bytes()["kv_blocks"]["bytes"] == 3 * 64
        alloc.trim_page(b, 2)
        assert dt.pool_bytes()["kv_blocks"]["bytes"] == 2 * 64
        alloc.share([b])
        copy = alloc.copy_block(b)  # COW: copy charged, source keeps a ref
        assert dt.pool_bytes()["kv_blocks"]["bytes"] == 4 * 64
        alloc.free([b, copy])
        assert dt.pool_bytes()["kv_blocks"]["bytes"] == 0.0
        assert dt.pool_bytes()["kv_blocks"]["peak"] == 4 * 64


# --------------------------------------------------------------------------
# Transfer ledger
# --------------------------------------------------------------------------
class TestTransfers:
    def test_ledger_tail(self):
        dt.record_transfer("h2d", 1000, src="unit_a")
        dt.record_transfer("d2h", 500, src="unit_b")
        rows = dt.transfer_records()
        assert [(r["direction"], r["bytes"], r["src"]) for r in rows] == \
            [("h2d", 1000, "unit_a"), ("d2h", 500, "unit_b")]

    def test_windowed_bandwidth(self):
        t0 = time.time()
        dt.record_transfer("h2d", 1, src="bw_unit")
        dt.transfer_bw("h2d", src="bw_unit", now=t0)  # baseline sample
        dt.record_transfer("h2d", 5999, src="bw_unit")
        bw = dt.transfer_bw("h2d", src="bw_unit", window_s=60.0,
                            now=t0 + 1.0)
        assert bw == pytest.approx(5999 / 60.0, rel=0.01)
        # Direction filter: nothing moved d2h on this source.
        assert dt.transfer_bw("d2h", src="bw_unit", now=t0 + 1.0) == 0.0

    def test_device_put_batch_hook(self):
        from ray_tpu._private import jax_compat

        batch = {"tokens": np.zeros((2, 8), np.int32),
                 "labels": ["a", "b"]}  # non-numeric stays on host
        out = jax_compat.device_put_batch(batch, transfer_src="unit_ingest")
        assert out["labels"] == ["a", "b"]
        rows = [r for r in dt.transfer_records()
                if r["src"] == "unit_ingest"]
        assert len(rows) == 1
        assert rows[0]["direction"] == "h2d"
        assert rows[0]["bytes"] == 2 * 8 * 4


# --------------------------------------------------------------------------
# The compile listener: jax's monitoring events under a compile label
# --------------------------------------------------------------------------
class TestCompileListener:
    def test_labelled_jit_compiles_once_then_classifies_shape_change(self):
        import jax
        import jax.numpy as jnp

        dt.listen_for_compiles()
        fn = jax.jit(lambda x: x * 2 + 1)

        def call(x):
            sign = lambda: ((tuple(x.shape), x.dtype), None, ())  # noqa: E731
            with dt.compile_label("unit_fn", sign) as label:
                out = fn(x)
            return out, label

        x3 = jnp.arange(3, dtype=jnp.float32)
        out, label = call(x3)
        np.testing.assert_allclose(np.asarray(out), [1.0, 3.0, 5.0])
        assert label.compiles == 1 and label.compile_s > 0
        _, label = call(x3)  # warm: jit's cache, no compile event
        assert label.compiles == 0
        rows = dt.compile_records("unit_fn")
        assert [r["trigger"] for r in rows] == [dt.TRIGGER_FIRST]
        assert rows[0]["compile_s"] > 0

        # A deliberate shape change recompiles and classifies as such.
        call(jnp.arange(4, dtype=jnp.float32))
        rows = dt.compile_records("unit_fn")
        assert [r["trigger"] for r in rows] == [dt.TRIGGER_FIRST,
                                                dt.TRIGGER_SHAPE]

    def test_unlabelled_compiles_are_unclassified_and_feed_no_storm(
            self, monkeypatch):
        import jax
        import jax.numpy as jnp

        monkeypatch.setenv("RAY_TPU_COMPILE_STORM_THRESHOLD", "2")
        dt.listen_for_compiles()
        for n in (5, 6, 7):
            jax.jit(lambda x: x - 3)(jnp.ones(n))
        rows = dt.compile_records(dt.UNLABELLED)
        assert len(rows) >= 3
        assert {r["trigger"] for r in rows} == {dt.TRIGGER_UNCLASSIFIED}
        assert dt.compile_totals()["storms"] == 0

    def test_listener_registers_once(self):
        import jax
        import jax.numpy as jnp

        dt.listen_for_compiles()
        dt.listen_for_compiles()
        x = jnp.ones(9)  # its own compile, outside the label
        with dt.compile_label("unit_once", lambda: (("s",), None, ())):
            jax.jit(lambda x: x * 5 - 1)(x)
        assert len(dt.compile_records("unit_once")) == 1

    def test_labels_nest_and_restore(self):
        with dt.compile_label("outer") as outer:
            with dt.compile_label("inner"):
                assert dt._thread.label.label == "inner"
            assert dt._thread.label is outer
        assert dt._thread.label is None

    def test_program_registry_and_first_calls(self):
        marker = object()
        dt.register_program("unit_prog", marker)
        assert dt.program("unit_prog") is marker
        assert dt.program("nobody") is None
        dt.record_first_call("unit_prog", 1.25, ts=10.0)
        assert dt.first_calls("unit_prog") == [
            {"label": "unit_prog", "ts": 10.0, "seconds": 1.25}]
        assert dt.snapshot()["compiles"]["first_calls"][-1]["seconds"] \
            == 1.25
        dt.reset()
        assert dt.program("unit_prog") is None and dt.first_calls() == []


# --------------------------------------------------------------------------
# Snapshot, bundle embedding, fault absorption, rollup
# --------------------------------------------------------------------------
class TestSnapshotAndRollup:
    def test_snapshot_is_json_serializable(self):
        dt.record_compile("f", shapes=("a",))
        dt.pool_add("kv_blocks", 100)
        dt.record_transfer("h2d", 10, src="unit")
        snap = dt.snapshot()
        doc = json.loads(json.dumps(snap))
        assert set(doc) == {"ts", "compiles", "pools", "transfers",
                            "device_memory"}
        assert doc["compiles"]["totals"]["compiles"] == 1
        assert doc["pools"]["kv_blocks"]["bytes"] == 100
        assert doc["transfers"]["tail"][-1]["bytes"] == 10

    def test_bundle_absorbs_snapshot_fault(self, recorder_env):
        """The device_telemetry_snapshot chaos point must cost the bundle
        only its device section, never the ring/stacks/timeseries."""
        _set_chaos("device_telemetry_snapshot=1:1")
        try:
            bundle = forensics.build_bundle()
            assert bundle["device_telemetry"] is None
            assert "timeseries" in bundle and "dumps" in bundle
            # Injector exhausted (max_failures=1): next bundle embeds.
            assert forensics.build_bundle()["device_telemetry"] is not None
        finally:
            _set_chaos("")

    def test_publish_rolls_up_to_collector(self):
        from ray_tpu.util.metrics_agent import TimeSeriesCollector

        dt.record_compile("f", shapes=("a",), trace_s=0.1, compile_s=0.2)
        dt.record_transfer("h2d", 100, src="pub_unit")
        collector = TimeSeriesCollector()
        dt.publish(collector, source="nodeA")
        names = collector.series_names()
        assert "ray_tpu_xla_compiles_total" in names
        assert "ray_tpu_device_transfer_bytes_total" in names

    def test_serve_accessor_resolves(self):
        """ray_tpu.serve.device.transfer_bw — the dotted accessor the
        registry-consistency checker maps to the transfer counter."""
        from ray_tpu import serve

        assert serve.device.transfer_bw is dt.transfer_bw


# --------------------------------------------------------------------------
# Perfetto "device" lane
# --------------------------------------------------------------------------
class TestDeviceLane:
    def test_device_plane_spans_share_the_device_pid(self):
        from ray_tpu._private.profiling import spans_to_chrome_events

        tracing.clear_spans()
        tracing.enable_tracing()
        try:
            t = time.time()
            dt.record_compile("f", shapes=("a",), trace_s=0.1, compile_s=0.2,
                              ts=t)
            dt.record_transfer("h2d", 64, src="unit", start=t - 0.5, end=t)
            spans = tracing.exported_spans()
        finally:
            tracing.disable_tracing()
            tracing.clear_spans()
        events = {e["name"]: e for e in spans_to_chrome_events(spans)}
        for name in ("xla.compile", "device.transfer"):
            assert events[name]["pid"] == "device"
        assert events["device.transfer"]["args"]["bytes"] == 64

    def test_burn_lane_entry_is_gone(self):
        assert "device.burn" not in tracing.SPAN_REGISTRY
        assert not hasattr(dt, "record_burn")


# --------------------------------------------------------------------------
# Satellite: compiled-router recompile reason label
# --------------------------------------------------------------------------
class TestRecompileReasonLabel:
    def test_counter_declares_reason_tag(self):
        from ray_tpu.serve import compiled_router

        assert compiled_router.RECOMPILES_TOTAL._tag_keys == \
            ("deployment", "reason")

    def test_deployment_state_stamps_change_reason(self):
        """The reconciler's reason plumbing: rows start as "deploy" and an
        autoscaler target change re-stamps them "autoscale" — the label the
        router attaches to its next recompile."""
        from ray_tpu.serve.deployment_state import (DeploymentInfo,
                                                    DeploymentState)

        class Dummy:
            pass

        state = DeploymentState(DeploymentInfo(name="d", app_name="a",
                                               deployment_def=Dummy))
        assert state.change_reason == "deploy"
        state.set_target_num(state.target_num + 1)
        assert state.change_reason == "autoscale"
        assert state._target_source == "autoscale"


# --------------------------------------------------------------------------
# scripts/check_bench_gates.py
# --------------------------------------------------------------------------
def _gates_module():
    import importlib.util

    path = os.path.join(REPO, "scripts", "check_bench_gates.py")
    spec = importlib.util.spec_from_file_location("check_bench_gates", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestCheckBenchGates:
    def test_all_committed_artifacts_hold(self):
        mod = _gates_module()
        for path in sorted(os.listdir(REPO)):
            if path.startswith("BENCH_") and path.endswith(".json"):
                assert mod.check_file(os.path.join(REPO, path)) == []

    def test_overhead_exceeding_gate_fails(self):
        mod = _gates_module()
        doc = {"overhead_pct": 3.1, "gate_pct": 2.0, "passed": True}
        violations = mod.collect_violations(doc)
        assert len(violations) == 1 and "exceeds gate" in violations[0]
        # The prefixed spelling gates its prefixed sibling, recursively.
        nested = {"inner": {"device_telemetry_overhead_pct": 0.4,
                            "device_telemetry_gate_pct": 1.0}}
        assert mod.collect_violations(nested) == []

    def test_named_gate_and_bool_gates(self):
        mod = _gates_module()
        doc = {"elastic_lost_steps_max": 5, "elastic_lost_steps_gate": 2,
               "gate_window_bounded": False, "passed": False}
        assert len(mod.collect_violations(doc)) == 3

    def test_stranded_gate_is_a_violation(self):
        mod = _gates_module()
        doc = {"renamed_overhead": 0.1, "gate_pct": 2.0}
        violations = mod.collect_violations(doc)
        assert len(violations) == 1
        assert "no numeric measured sibling" in violations[0]

    def test_main_exits_nonzero_on_violation(self, tmp_path, capsys):
        mod = _gates_module()
        bad = tmp_path / "BENCH_BAD.json"
        bad.write_text(json.dumps({"overhead_pct": 9.0, "gate_pct": 1.0}))
        assert mod.main([str(bad)]) == 1
        assert "FAIL BENCH_BAD.json" in capsys.readouterr().out
        good = tmp_path / "BENCH_GOOD.json"
        good.write_text(json.dumps({"overhead_pct": 0.5, "gate_pct": 1.0,
                                    "passed": True}))
        assert mod.main([str(good)]) == 0
