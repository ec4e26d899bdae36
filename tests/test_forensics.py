"""Flight recorder + crash forensics (docs/observability.md).

Bottom-up:

* the black-box ring itself (seqlock wraparound, span tap, metric deltas),
* postmortem dumps (schema, heap gating, flood control, the
  ``forensics_dump`` chaos point, trigger absorption),
* the hang/straggler watchdog under a deterministic clock (beat/phase
  stall thresholds, one-shot reporting + re-arm, retirement, dispersion)
  plus a REAL wedged thread the liveness poll would call healthy,
* the stack profiler's never-writing-pid regression (S1),
* head-side forensics: index/load, bundles, the fused Perfetto timeline,
  the ``/api/postmortems`` routes and ``util.state`` listings,
* end-to-end chaos: a replica kill under compiled load and an elastic
  node preemption must each leave a complete postmortem bundle behind —
  the victim process's final spans, all-thread stacks and a death marker
  on the fused timeline.
"""

import json
import os
import subprocess
import sys
import threading
import time
import tracemalloc

import numpy as np
import pytest

from ray_tpu._private import stack_profiler
from ray_tpu.util import flight_recorder, forensics, tracing, watchdog
from ray_tpu.util.flight_recorder import FlightRecorder
from ray_tpu.util.watchdog import HangWatchdog


def _set_chaos(spec: str) -> None:
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu._private.fault_injection import reset_injector

    GLOBAL_CONFIG.testing_rpc_failure = spec
    reset_injector()


@pytest.fixture
def recorder_env(monkeypatch, tmp_path):
    """Isolated postmortem dir + fresh recorder/watchdog singletons, no
    background detection thread (units drive tick() with injected clocks)."""
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
# Ring buffer
# --------------------------------------------------------------------------
class TestRing:
    def test_wraparound_keeps_newest_and_counts_lifetime(self):
        rec = FlightRecorder(slots=16)
        for i in range(40):
            rec.record_event(f"e{i}", now=float(i))
        assert rec.events_recorded() == 40
        rows = rec.snapshot()
        assert len(rows) == 16
        # Oldest 24 overwritten; survivors ordered oldest-first.
        assert [r["seq"] for r in rows] == list(range(24, 40))
        assert rows[0]["name"] == "e24" and rows[-1]["name"] == "e39"

    def test_snapshot_skips_in_progress_slots(self):
        rec = FlightRecorder(slots=16)
        rec.record_event("ok", now=1.0)
        # Simulate a writer caught mid-fill: negative seq stamp.
        rec._ring[5][0] = -7
        rows = rec.snapshot()
        assert [r["name"] for r in rows] == ["ok"]

    def test_span_tap_records_open_and_closed_spans(self):
        rec = FlightRecorder()
        rec.tap_span({"name": "serve.request", "start": 1.0, "end": 2.5,
                      "status": "OK"})
        rec.tap_span({"name": "serve.route", "start": 3.0, "end": None,
                      "status": "OK"})
        rows = rec.snapshot()
        assert [r["kind"] for r in rows] == ["span", "span"]
        assert rows[0]["end"] == 2.5
        assert rows[1]["end"] == rows[1]["start"] == 3.0  # open span

    def test_singleton_taps_live_tracing(self, recorder_env):
        rec = flight_recorder.get_recorder()
        assert rec is not None
        tracing.enable_tracing()
        tracing.record_span("unit.span", 1.0, 2.0)
        spans = [r for r in rec.snapshot() if r["kind"] == "span"]
        assert any(r["name"] == "unit.span" for r in spans)

    def test_disabled_via_env(self, recorder_env, monkeypatch):
        monkeypatch.setenv("RAY_TPU_FLIGHT_RECORDER", "0")
        flight_recorder.reset_recorder()
        assert flight_recorder.get_recorder() is None
        assert flight_recorder.trigger_dump("nope") is None
        flight_recorder.record_event("noop")  # must not raise

    def test_sample_metric_deltas_records_counter_movement(self, recorder_env):
        rec = FlightRecorder()
        rec.record_event("seed", now=1.0)  # bumps the ring-events counter
        assert rec.sample_metric_deltas(now=2.0) >= 1
        metric_rows = [r for r in rec.snapshot() if r["kind"] == "metric"]
        assert any(r["name"] == "ray_tpu_forensics_ring_events_total"
                   and r["detail"] >= 1 for r in metric_rows)
        # No movement since the last sample -> no new delta row: of the
        # counter this test moves.  (The registry is the process's: threads
        # that earlier tests of this worker left running still move others.)
        def own_rows():
            return len([r for r in rec.snapshot() if r["kind"] == "metric"
                        and r["name"] == "ray_tpu_forensics_ring_events_total"])

        before = own_rows()
        rec.sample_metric_deltas(now=3.0)
        assert own_rows() == before == 1


# --------------------------------------------------------------------------
# Postmortem dumps
# --------------------------------------------------------------------------
class TestDump:
    def test_dump_schema_and_filename(self, recorder_env):
        rec = FlightRecorder()
        rec.record_event("last_breath", {"rid": "r0"}, now=10.0)
        path = rec.dump("unit reason/x", extra={"a": 1})
        assert path is not None and os.path.exists(path)
        assert os.path.basename(path) == f"{os.getpid()}-unit_reason_x.json"
        with open(path) as f:
            dump = json.load(f)
        assert dump["schema"] == 1
        assert dump["pid"] == os.getpid()
        assert dump["reason"] == "unit reason/x"
        assert dump["extra"] == {"a": 1}
        assert dump["events_recorded"] >= 1
        assert any(r["name"] == "last_breath" for r in dump["ring"])
        # All-thread stacks are always present; this thread is among them.
        assert dump["stacks"]
        assert any("MainThread" in name for name in dump["stacks"])
        # S2: no heap section when tracemalloc was not already tracing.
        assert dump["tracing_active"] is False
        assert "heap" not in dump

    def test_heap_only_when_tracemalloc_already_tracing(self, recorder_env):
        rec = FlightRecorder()
        was = tracemalloc.is_tracing()
        tracemalloc.start()
        try:
            with open(rec.dump("traced")) as f:
                dump = json.load(f)
        finally:
            if not was:
                tracemalloc.stop()
        assert dump["tracing_active"] is True
        assert "current_bytes" in dump["heap"] or dump["heap"]

    def test_flood_control_suppresses_repeats_per_reason(self, recorder_env,
                                                         monkeypatch):
        monkeypatch.setenv("RAY_TPU_POSTMORTEM_MIN_INTERVAL_S", "100")
        rec = FlightRecorder()
        assert rec.dump("crashloop", now=1000.0) is not None
        assert rec.dump("crashloop", now=1001.0) is None  # suppressed
        # A different reason has its own clock.
        assert rec.dump("other", now=1001.0) is not None
        # Past the window the same reason dumps again.
        assert rec.dump("crashloop", now=1200.0) is not None

    def test_forensics_dump_fault_point_absorbed_by_trigger(self,
                                                            recorder_env):
        from ray_tpu._private.fault_injection import InjectedFailure

        _set_chaos("forensics_dump=1.0")
        try:
            rec = FlightRecorder()
            with pytest.raises(InjectedFailure):
                rec.dump("direct")  # the raw API surfaces chaos
            # Every trigger site goes through trigger_dump, which absorbs:
            # a forensics failure must never worsen the failure being
            # recorded.
            assert flight_recorder.trigger_dump("absorbed") is None
        finally:
            _set_chaos("")

    def test_trigger_dump_records_trigger_event_and_emits_span(
            self, recorder_env):
        tracing.enable_tracing()
        path = flight_recorder.trigger_dump("unit_trigger", {"k": 1})
        assert path is not None
        with open(path) as f:
            dump = json.load(f)
        trig = [r for r in dump["ring"] if r["kind"] == "trigger"]
        assert trig and trig[-1]["name"] == "unit_trigger"
        names = [s["name"] for s in tracing.exported_spans()]
        assert "forensics.dump" in names


# --------------------------------------------------------------------------
# Hang/straggler watchdog (deterministic clock)
# --------------------------------------------------------------------------
class TestWatchdog:
    def test_beat_stall_one_shot_and_rearm(self, recorder_env):
        wd = HangWatchdog(stall_threshold_s=10.0)
        wd.beat("w0", now=0.0)
        assert wd.tick(now=5.0) == []
        stalls = wd.tick(now=11.0)
        assert len(stalls) == 1
        assert stalls[0]["source"] == "w0" and stalls[0]["kind"] == "beat"
        assert stalls[0]["since"] == 0.0
        # One-shot: the same wedge is not re-reported every tick.
        assert wd.tick(now=12.0) == []
        # Progress re-arms detection; a later wedge is reported again.
        wd.beat("w0", now=13.0)
        assert wd.tick(now=14.0) == []
        assert [s["kind"] for s in wd.tick(now=30.0)] == ["beat"]

    def test_phase_stall_even_while_beats_continue(self, recorder_env):
        wd = HangWatchdog(stall_threshold_s=10.0)
        wd.phase_enter("r0", "rendezvous", now=0.0)
        wd.beat("r0", now=8.0)  # other threads still look alive
        stalls = wd.tick(now=11.0)
        assert [s["kind"] for s in stalls] == ["phase"]
        assert stalls[0]["phase"] == "rendezvous"
        assert stalls[0]["since"] == 0.0
        # Leaving the phase clears the wedge.
        wd.phase_exit("r0", now=12.0)
        assert wd.tick(now=13.0) == []

    def test_quiet_source_retires_instead_of_stalling_forever(
            self, recorder_env):
        wd = HangWatchdog(stall_threshold_s=10.0)
        wd.beat("done", now=0.0)
        # Far past the retirement horizon: popped, not reported.
        assert wd.tick(now=150.0) == []
        assert "done" not in wd.straggler_report()

    def test_forget_drops_source(self, recorder_env):
        wd = HangWatchdog(stall_threshold_s=10.0)
        wd.beat("lane", now=0.0)
        wd.forget("lane")
        assert wd.tick(now=100.0) == []

    def test_straggler_flagged_from_dispersion(self, recorder_env):
        wd = HangWatchdog(stall_threshold_s=100.0, straggler_factor=2.0)
        for _ in range(5):
            wd.beat("a", wall=1.0, now=0.0)
            wd.beat("b", wall=1.1, now=0.0)
            wd.beat("c", wall=5.0, now=0.0)
        wd.tick(now=1.0)
        rep = wd.straggler_report()
        assert rep["c"]["straggler"] is True
        assert rep["a"]["straggler"] is False
        assert rep["b"]["straggler"] is False
        assert rep["c"]["median_wall"] == 5.0

    def test_single_source_never_a_straggler(self, recorder_env):
        wd = HangWatchdog(stall_threshold_s=100.0)
        wd.beat("solo", wall=9.0, now=0.0)
        wd.tick(now=1.0)
        assert wd.straggler_report()["solo"]["straggler"] is False

    def test_stall_captures_stacks_into_ring_and_emits_error_span(
            self, recorder_env):
        rec = flight_recorder.get_recorder()
        tracing.enable_tracing()
        wd = HangWatchdog(stall_threshold_s=5.0)
        wd.phase_enter("w1", "collective", now=100.0)
        stalls = wd.tick(now=200.0)
        assert len(stalls) == 1
        # The black box holds the stall with all-thread stacks attached.
        stall_rows = [r for r in rec.snapshot() if r["kind"] == "stall"]
        assert stall_rows and stall_rows[-1]["name"] == "stall:w1"
        assert stall_rows[-1]["status"] == "ERROR"
        assert any("MainThread" in n for n in stall_rows[-1]["detail"]["stacks"])
        # Retroactive ERROR span so the wedge renders on the timeline.
        spans = [s for s in tracing.exported_spans()
                 if s["name"] == "train.stall"]
        assert spans and spans[0]["status"] == "ERROR: Stall"
        assert spans[0]["start"] == 100.0 and spans[0]["end"] == 200.0

    def test_wedged_thread_flagged_while_liveness_says_alive(
            self, recorder_env):
        """Acceptance: a worker wedged inside a bounded phase is ALIVE (a
        liveness poll sees a healthy thread) yet the watchdog flags it."""
        wd = HangWatchdog(stall_threshold_s=0.2)
        release = threading.Event()
        entered = threading.Event()

        def wedged_worker():
            wd.phase_enter("wedged", "rendezvous")
            entered.set()
            release.wait(timeout=30)  # stuck "in the collective"
            wd.phase_exit("wedged")

        t = threading.Thread(target=wedged_worker, daemon=True)
        t.start()
        assert entered.wait(timeout=10)
        try:
            stalls = wd.tick(now=time.time() + 1.0)
            assert t.is_alive(), "victim must be alive when flagged"
            assert [s["source"] for s in stalls] == ["wedged"]
        finally:
            release.set()
            t.join(timeout=10)


# --------------------------------------------------------------------------
# Stack profiler regression (S1): a pid that never writes must not hang
# --------------------------------------------------------------------------
class TestStackProfiler:
    def test_current_process_stacks_sees_this_thread(self):
        stacks = stack_profiler.current_process_stacks()
        assert any("MainThread" in name for name in stacks)

    def test_never_writing_pid_returns_at_deadline_with_sentinel(
            self, monkeypatch, tmp_path):
        """A worker that masks SIGUSR1 (or is wedged in native code) never
        appends to its dump file; the collector must return at the TOTAL
        deadline with the sentinel, not poll forever."""
        monkeypatch.setenv("RAY_TPU_STACK_DUMP_DIR", str(tmp_path))
        code = ("import signal, sys, time\n"
                "signal.signal(signal.SIGUSR1, signal.SIG_IGN)\n"
                "print('ready', flush=True)\n"
                "time.sleep(60)\n")
        proc = subprocess.Popen([sys.executable, "-c", code],
                                stdout=subprocess.PIPE)
        try:
            assert proc.stdout.readline().strip() == b"ready"
            # The handler-registration file exists (so the signal gate
            # passes) but the worker will never write past the mark.
            (tmp_path / f"{proc.pid}.txt").write_text("")
            t0 = time.monotonic()
            res = stack_profiler.dump_worker_stacks([proc.pid],
                                                    timeout_s=0.5)
            elapsed = time.monotonic() - t0
        finally:
            proc.kill()
            proc.wait()
        assert elapsed < 5.0, "collector blocked past its deadline"
        assert res[proc.pid].startswith(stack_profiler.MISSING_DUMP_PREFIX)

    def test_dead_pid_reported_unreachable(self, monkeypatch, tmp_path):
        monkeypatch.setenv("RAY_TPU_STACK_DUMP_DIR", str(tmp_path))
        proc = subprocess.Popen([sys.executable, "-c", "pass"])
        proc.wait()
        (tmp_path / f"{proc.pid}.txt").write_text("")
        res = stack_profiler.dump_worker_stacks([proc.pid], timeout_s=0.5)
        assert res[proc.pid].startswith("<")  # unreachable or deadline


# --------------------------------------------------------------------------
# Head-side forensics: index, bundle, fused timeline, API routes
# --------------------------------------------------------------------------
class TestForensics:
    def _two_dumps(self):
        rec = FlightRecorder()
        rec.tap_span({"name": "serve.request", "start": 1.0, "end": 2.0,
                      "status": "OK"})
        rec.record_event("stall:w0", {"stacks": {}}, now=3.0, kind="stall",
                         status="ERROR")
        p1 = rec.dump("first", now=10.0)
        p2 = rec.dump("second", now=20.0)
        return rec, p1, p2

    def test_list_newest_first_and_counts(self, recorder_env):
        self._two_dumps()
        rows = forensics.list_postmortems()
        assert [r["reason"] for r in rows] == ["second", "first"]
        assert all(r["pid"] == os.getpid() for r in rows)
        assert rows[0]["stalls"] == 1
        assert rows[0]["ring_events"] >= 2

    def test_torn_dump_skipped_not_fatal(self, recorder_env):
        self._two_dumps()
        (recorder_env / "999-torn.json").write_text('{"pid": 1, "re')
        rows = forensics.list_postmortems()
        assert len(rows) == 2  # the torn file is silently skipped

    def test_load_roundtrip_and_traversal_guard(self, recorder_env):
        self._two_dumps()
        pm_id = forensics.list_postmortems()[0]["id"]
        dump = forensics.load_postmortem(pm_id)
        assert dump is not None and dump["reason"] == "second"
        assert forensics.load_postmortem("no-such-id") is None
        assert forensics.load_postmortem("../../etc/passwd") is None
        assert forensics.load_postmortem(".hidden") is None

    def test_bundle_merges_dumps_stalls_timeseries_runs(self, recorder_env):
        self._two_dumps()
        bundle = forensics.build_bundle(window_s=60.0)
        assert bundle["schema"] == 1
        assert len(bundle["dumps"]) == 2
        assert all("id" in d for d in bundle["dumps"])
        # Stalls hoisted across all dumps for the cluster-level story.
        assert any(r["name"] == "stall:w0" for r in bundle["stalls"])
        assert "series" in bundle["timeseries"]
        assert isinstance(bundle["train_runs"], list)

    def test_fused_timeline_has_lanes_and_death_markers(self, recorder_env):
        self._two_dumps()
        bundle = forensics.build_bundle()
        events = forensics.bundle_chrome_trace(bundle)
        assert events
        pids = {e["pid"] for e in events}
        assert f"pid:{os.getpid()}" in pids
        # One duration event per ring span, instant markers for the rest.
        assert any(e["ph"] == "X" and e["name"] == "serve.request"
                   for e in events)
        stall_marks = [e for e in events
                       if e["ph"] == "i" and "stall:w0" in e["name"]]
        assert stall_marks and stall_marks[0].get("cname") == "terrible"
        # The dump trigger itself is a marker on every lane.
        assert any(e["ph"] == "i" and e["name"] == "dump:second"
                   for e in events)

    def test_api_routes_serve_index_detail_and_bundle(self, recorder_env):
        from ray_tpu._private.metrics_agent import _api_payload

        self._two_dumps()
        rows = _api_payload(None, "/api/postmortems")
        assert [r["reason"] for r in rows] == ["second", "first"]
        detail = _api_payload(None, f"/api/postmortems/{rows[0]['id']}")
        assert detail["reason"] == "second"
        bundle = _api_payload(None, "/api/postmortems/bundle")
        assert len(bundle["dumps"]) == 2

    def test_state_api_listing_and_filters(self, recorder_env):
        from ray_tpu.util import state

        self._two_dumps()
        rows = state.list_postmortems(filters=[("reason", "=", "first")])
        assert len(rows) == 1 and rows[0]["reason"] == "first"
        dump = state.get_postmortem(rows[0]["id"])
        assert dump is not None and dump["reason"] == "first"


def test_init_bootstraps_black_box_without_tracing(monkeypatch, tmp_path):
    """Default config (tracing off): init itself arms the recorder, anchors
    the ring with a runtime.start state row, and starts the watchdog ticker
    — a process that crashes right after startup must dump a populated
    ring, not an empty buffer."""
    import ray_tpu

    monkeypatch.setenv("RAY_TPU_POSTMORTEM_DIR", str(tmp_path / "pm"))
    ray_tpu.shutdown()
    flight_recorder.reset_recorder()
    watchdog.reset_watchdog()
    ray_tpu.init(num_cpus=2)
    try:
        rec = flight_recorder.get_recorder()
        assert tracing._tap is not None
        rows = rec.snapshot()
        assert any(r["kind"] == "state" and r["name"] == "runtime.start"
                   for r in rows)
        wd = watchdog.get_watchdog()
        assert wd._thread is not None and wd._thread.is_alive()
        # Counter movement from startup reaches the ring on the next tick
        # even with tracing off.
        wd.tick()
        assert any(r["kind"] == "metric" for r in rec.snapshot())
    finally:
        ray_tpu.shutdown()
        flight_recorder.reset_recorder()
        watchdog.reset_watchdog()


# --------------------------------------------------------------------------
# End-to-end chaos: kill / preemption -> complete postmortem bundle
# --------------------------------------------------------------------------
from chaos_utils import kill_one_replica, wait_for_postmortem  # noqa: E402


@pytest.fixture
def forensics_serve(monkeypatch, tmp_path):
    """Serve instance with an isolated postmortem dir and live tracing (so
    the victim's spans flow through the tap into the black box)."""
    import ray_tpu
    from ray_tpu import serve

    monkeypatch.setenv("RAY_TPU_POSTMORTEM_DIR", str(tmp_path / "pm"))
    monkeypatch.setenv("RAY_TPU_POSTMORTEM_MIN_INTERVAL_S", "0")
    monkeypatch.setenv("RAY_TPU_SERVE_COMPILED_STABLE_S", "0.2")
    flight_recorder.reset_recorder()
    watchdog.reset_watchdog()
    # Re-arm the tap NOW: init(ignore_reinit_error=True) may reuse a live
    # runtime and skip the Runtime.__init__ bootstrap, and the serve spans
    # this fixture exists to capture flow before any trigger site would
    # lazily build the recorder.
    flight_recorder.get_recorder()
    tracing.clear_spans()
    tracing.enable_tracing()
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    serve.start(http_options={"port": 0})
    yield
    serve.shutdown()
    ray_tpu.shutdown()
    tracing.disable_tracing()
    tracing.clear_spans()
    flight_recorder.reset_recorder()
    watchdog.reset_watchdog()


def test_kill_under_compiled_load_leaves_postmortem(forensics_serve):
    """Acceptance: SIGKILL a replica under compiled load — the fallback
    trigger fires a dump whose ring holds the victim runtime's final spans
    and whose stacks cover every thread, and the fused timeline carries
    the death marker."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=3, max_ongoing_requests=16,
                      health_check_period_s=0.2)
    class Echo:
        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.002)
        async def __call__(self, items):
            return [x * 2 for x in items]

    handle = serve.run(Echo.bind(), name="fkill", route_prefix=None)
    assert handle.remote(1).result(timeout_s=30) == 2
    router = handle._get_router()
    deadline = time.time() + 10
    while router._compiled.mode != "compiled" and time.time() < deadline:
        time.sleep(0.05)
    assert router._compiled.mode == "compiled", "route never compiled"

    stop = threading.Event()

    def client():
        i = 0
        while not stop.is_set():
            try:
                handle.remote(i).result(timeout_s=15)
            except Exception:
                pass  # recovery is test_serve_chaos's bar; forensics is ours
            i += 1

    threads = [threading.Thread(target=client, daemon=True) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    try:
        kill_one_replica()
        # The compiled graph tears down -> the fallback trigger dumps.
        row = wait_for_postmortem("compiled_fallback", timeout_s=30.0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=20)
    assert row is not None, \
        f"no compiled_fallback postmortem: {forensics.list_postmortems()}"
    dump = forensics.load_postmortem(row["id"])
    # The black box kept the victim's final spans: serve traffic that was
    # in flight when the replica died.
    span_rows = [r for r in dump["ring"] if r["kind"] == "span"]
    assert span_rows, "ring lost the victim's final spans"
    # All-thread stacks at the moment of death.
    assert dump["stacks"] and any("MainThread" in n for n in dump["stacks"])
    # The trigger itself is on the record.
    assert any(r["kind"] == "trigger" and r["name"] == "compiled_fallback"
               for r in dump["ring"])
    assert dump["extra"]["deployment"]
    # The actor-death sentinel fired its own dump for the killed replica.
    assert wait_for_postmortem("actor_death", timeout_s=20.0) is not None
    # Fused timeline: the death marker renders next to the final spans.
    events = forensics.bundle_chrome_trace(forensics.build_bundle())
    assert any(e["ph"] == "i" and e["name"] == "dump:compiled_fallback"
               for e in events)
    assert any(e["ph"] == "X" for e in events)


@pytest.fixture
def forensics_elastic(monkeypatch, tmp_path):
    """0-CPU head + 3 preemptible worker nodes with an isolated postmortem
    dir and live tracing (same topology as test_train_elastic)."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    monkeypatch.setenv("RAY_TPU_POSTMORTEM_DIR", str(tmp_path / "pm"))
    monkeypatch.setenv("RAY_TPU_POSTMORTEM_MIN_INTERVAL_S", "0")
    flight_recorder.reset_recorder()
    watchdog.reset_watchdog()
    flight_recorder.get_recorder()  # re-arm the tap after the reset
    tracing.clear_spans()
    tracing.enable_tracing()
    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 0})
    nodes = [cluster.add_node(num_cpus=1) for _ in range(3)]
    yield cluster, nodes
    ray_tpu.shutdown()
    tracing.disable_tracing()
    tracing.clear_spans()
    flight_recorder.reset_recorder()
    watchdog.reset_watchdog()
    _set_chaos("")


def test_node_preemption_leaves_postmortem(forensics_elastic, tmp_path):
    """Acceptance: preempt a worker node mid-fit — the elastic shrink path
    dumps a postmortem whose ring holds the run's final train/collective
    spans and all-thread stacks, with the preemption marker on the fused
    timeline; the run itself still completes exactly-once."""
    from ray_tpu.autoscaler.elastic import simulate_preemption
    from ray_tpu.train import (
        CheckpointConfig, ElasticConfig, FailureConfig, JaxTrainer,
        RunConfig, ScalingConfig)
    from test_train_elastic import _elastic_loop

    cluster, nodes = forensics_elastic
    data = np.arange(1, 241, dtype=np.float64)
    trainer = JaxTrainer(
        _elastic_loop,
        train_loop_config={},
        scaling_config=ScalingConfig(
            num_workers=3, worker_mode="threads",
            elastic=ElasticConfig(min_workers=1, grow_check_period_s=0.3)),
        datasets={"train": data},
        run_config=RunConfig(
            name="forensics", storage_path=str(tmp_path / "ckpt"),
            checkpoint_config=CheckpointConfig(async_save=True,
                                               replica_memory_steps=2),
            failure_config=FailureConfig(max_failures=3)))
    box = {}

    def run():
        box["result"] = trainer.fit()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    time.sleep(1.5)
    assert simulate_preemption(str(nodes[0])) is not None
    row = wait_for_postmortem("elastic_preempt", timeout_s=60.0)
    t.join(timeout=120)
    assert not t.is_alive(), "fit() hung after preemption"
    assert box["result"].error is None, box["result"].error

    assert row is not None, \
        f"no elastic_preempt postmortem: {forensics.list_postmortems()}"
    dump = forensics.load_postmortem(row["id"])
    assert dump["extra"]["run"] == "forensics"
    assert dump["extra"]["event"]
    # Final spans of the run that was interrupted, and stacks at the dump.
    span_rows = [r for r in dump["ring"] if r["kind"] == "span"]
    assert span_rows, "ring lost the run's final spans"
    assert dump["stacks"] and any("MainThread" in n for n in dump["stacks"])
    assert any(r["kind"] == "trigger" and r["name"] == "elastic_preempt"
               for r in dump["ring"])
    # Step heartbeats reached the watchdog from the training workers.
    rep = watchdog.get_watchdog().straggler_report()
    assert any(s.startswith("train:forensics:") for s in rep), rep
    # Fused timeline: preemption marker plus the final span lanes.
    events = forensics.bundle_chrome_trace(forensics.build_bundle())
    assert any(e["ph"] == "i" and e["name"] == "dump:elastic_preempt"
               for e in events)
    assert any(e["ph"] == "X" for e in events)
