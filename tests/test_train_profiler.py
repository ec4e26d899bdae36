"""Train-step profiler, time-series rollups, and the unified state view.

Covers the observability plane bottom-up (docs/observability.md):

* ``Counter.inc(0)`` as a no-op (negatives still raise) — the contract
  the zero-byte ingest paths rely on;
* ``StepProfiler`` attribution under a deterministic clock: buckets sum
  to the wall by construction, live gauges refresh, spans parent under
  ``train.step``;
* the hook shims (``sys.modules`` probe) feeding it from the data layer;
* ``TimeSeriesAggregator`` windowed rates/percentiles under a
  deterministic feed, snapshot shipping into the ``TimeSeriesCollector``,
  and the OpenMetrics exposition;
* the run registry + ``list_train_runs()`` state API;
* timeline fusion: one elastic shrink→grow fit() with tracing on renders
  a Perfetto-loadable trace whose shared "train" lane holds step, wait,
  elastic-recovery and checkpoint spans together;
* the agent's ``/timeseries`` and ``/api/train_runs`` HTTP routes.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu.autoscaler.elastic import simulate_preemption
from ray_tpu.cluster_utils import Cluster
from ray_tpu.train import (
    CheckpointConfig,
    ElasticConfig,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
    StepProfiler,
)
from ray_tpu.train import metrics as train_metrics
from ray_tpu.train import profiler as train_profiler
from ray_tpu.train import run_registry
from ray_tpu.util import metrics as um
from ray_tpu.util import state as state_api
from ray_tpu.util import tracing
from ray_tpu.util.metrics_agent import (
    TimeSeriesAggregator,
    TimeSeriesCollector,
)


# --------------------------------------------------------------------------
# Counter.inc(0): no-op, not an error
# --------------------------------------------------------------------------
class TestCounterZeroInc:
    def test_inc_zero_is_noop(self):
        c = um.Counter("test_zero_inc_total", "zero-inc contract")
        c.inc(0)
        assert c.get() == 0.0
        c.inc(2)
        c.inc(0)
        assert c.get() == 2.0

    def test_negative_still_raises(self):
        c = um.Counter("test_neg_inc_total", "negatives stay fatal")
        with pytest.raises(ValueError):
            c.inc(-1)
        with pytest.raises(ValueError):
            c.inc(-0.5)


# --------------------------------------------------------------------------
# StepProfiler under a deterministic clock
# --------------------------------------------------------------------------
class TestStepProfiler:
    def test_buckets_sum_to_wall_by_construction(self):
        p = StepProfiler(run_name="t", rank=0)
        p.start(now=100.0)
        p.record("data_wait", 100.0, 100.3)
        p.record("h2d", 100.3, 100.4)
        p.record("collective", 100.6, 100.8)
        row = p.step_boundary(now=101.0)
        assert row["wall"] == pytest.approx(1.0)
        assert row["data_wait"] == pytest.approx(0.3)
        assert row["h2d"] == pytest.approx(0.1)
        assert row["collective"] == pytest.approx(0.2)
        assert row["ckpt_block"] == 0.0
        measured = sum(row[b] for b in train_profiler.BUCKETS)
        assert row["compute"] == pytest.approx(row["wall"] - measured)
        total = measured + row["compute"]
        assert total == pytest.approx(row["wall"])

    def test_overlong_bucket_clamped_and_compute_floored(self):
        p = StepProfiler()
        p.start(now=10.0)
        # A hook interval longer than the step (clock skew / overlapping
        # windows) must not produce negative compute.
        p.record("data_wait", 9.0, 12.0)
        row = p.step_boundary(now=11.0)
        assert row["data_wait"] == pytest.approx(row["wall"])
        assert row["compute"] == 0.0

    def test_boundary_resets_and_steps_advance(self):
        p = StepProfiler()
        p.start(now=0.0)
        p.record("data_wait", 0.0, 0.5)
        r0 = p.step_boundary(now=1.0)
        r1 = p.step_boundary(now=2.0)
        assert (r0["step"], r1["step"]) == (0, 1)
        assert r1["data_wait"] == 0.0, "bucket totals leaked across steps"
        assert len(p.history) == 2
        assert p.last_attribution()["step"] == 1

    def test_zero_or_negative_window_returns_none(self):
        p = StepProfiler()
        assert p.step_boundary(now=5.0) is None  # never started
        p.start(now=5.0)
        assert p.step_boundary(now=5.0) is None  # empty window

    def test_gauges_refresh_on_boundary(self):
        p = StepProfiler(flops_per_step=2e9, tokens_per_step=1000,
                         peak_flops=1e12)
        p.start(now=0.0)
        p.record("data_wait", 0.0, 0.5)
        p.step_boundary(now=2.0)
        assert train_metrics.DATA_STARVED_FRACTION.get() == pytest.approx(0.25)
        assert train_metrics.TOKENS_PER_SECOND.get() == pytest.approx(500.0)
        assert train_metrics.MFU.get() == pytest.approx(2e9 / 2.0 / 1e12)
        assert train_metrics.STEP_P50_SECONDS.get() == pytest.approx(2.0)
        assert train_metrics.STEP_BUCKET_SECONDS.get(
            {"bucket": "data_wait"}) == pytest.approx(0.5)

    def test_spans_parent_under_train_step(self):
        tracing.clear_spans()
        tracing.enable_tracing()
        try:
            p = StepProfiler(run_name="spantest", rank=3)
            p.start(now=50.0)
            p.record("data_wait", 50.0, 50.2)
            p.record("collective", 50.4, 50.5)
            p.step_boundary(now=51.0)
            spans = {s["name"]: s for s in tracing.exported_spans()}
        finally:
            tracing.disable_tracing()
            tracing.clear_spans()
        parent = spans["train.step"]
        assert parent["attributes"]["rank"] == 3
        assert "train.compute" not in spans  # the residual is no interval
        for child in ("train.data_wait", "train.collective"):
            assert spans[child]["parent_id"] == parent["span_id"], child
            assert spans[child]["trace_id"] == parent["trace_id"]

    def test_no_spans_when_tracing_off(self):
        tracing.clear_spans()
        p = StepProfiler()
        p.start(now=0.0)
        p.record("h2d", 0.0, 0.1)
        p.step_boundary(now=1.0)
        assert tracing.exported_spans() == []


# --------------------------------------------------------------------------
# Hook shims: the data layer reaches the profiler without importing train/
# --------------------------------------------------------------------------
class TestProfilerHooks:
    def test_shim_is_noop_without_active_profiler(self):
        from ray_tpu.data.ingest import prefetch

        train_profiler.activate(None)
        prefetch._profiler_record("data_wait", 0.0, 1.0)  # must not raise

    def test_shim_feeds_active_profiler(self):
        from ray_tpu.data.ingest import prefetch

        p = StepProfiler()
        train_profiler.activate(p)
        try:
            t = time.time()
            prefetch._profiler_record("h2d", t - 0.25, t)
        finally:
            train_profiler.activate(None)
        assert p._totals["h2d"] == pytest.approx(0.25)

    def test_starved_prefetcher_records_data_wait(self):
        from ray_tpu.data.ingest.prefetch import HostPrefetcher

        def slow_src():
            for i in range(3):
                time.sleep(0.08)
                yield i

        p = StepProfiler()
        train_profiler.activate(p)
        try:
            assert list(HostPrefetcher(slow_src(), depth=2)) == [0, 1, 2]
            row = p.step_boundary()
        finally:
            train_profiler.activate(None)
        assert row is not None and row["data_wait"] > 0.05, row


# --------------------------------------------------------------------------
# TimeSeriesAggregator: deterministic feed
# --------------------------------------------------------------------------
class TestTimeSeriesAggregator:
    def test_counter_rate_from_positive_deltas(self):
        agg = TimeSeriesAggregator()
        for i in range(7):  # total climbs 50/sample, one sample per 10s
            agg.observe("req_total", 50.0 * i, {"d": "a"}, kind="counter",
                        ts=1000.0 + 10.0 * i)
        assert agg.window_rate("req_total", {"d": "a"}, window_s=60.0,
                               now=1060.0) == pytest.approx(5.0)

    def test_counter_reset_never_negative(self):
        agg = TimeSeriesAggregator()
        agg.observe("req_total", 100.0, kind="counter", ts=1000.0)
        agg.observe("req_total", 3.0, kind="counter", ts=1010.0)  # restart
        agg.observe("req_total", 9.0, kind="counter", ts=1020.0)
        rate = agg.window_rate("req_total", window_s=30.0, now=1020.0)
        assert rate == pytest.approx(6.0 / 30.0)
        assert rate >= 0.0

    def test_value_rate_and_gauge_mean(self):
        agg = TimeSeriesAggregator()
        for i in range(5):
            agg.observe("batch_rows", 20.0, kind="value", ts=100.0 + i)
            agg.observe("util", 0.5 + 0.1 * i, kind="gauge", ts=100.0 + i)
        assert agg.window_rate("batch_rows", window_s=10.0,
                               now=104.0) == pytest.approx(10.0)
        assert agg.window_rate("util", window_s=10.0,
                               now=104.0) == pytest.approx(0.7)

    def test_window_excludes_old_points(self):
        agg = TimeSeriesAggregator()
        agg.observe("v", 1000.0, kind="value", ts=0.0)
        agg.observe("v", 6.0, kind="value", ts=95.0)
        assert agg.window_sum("v", window_s=10.0,
                              now=100.0) == pytest.approx(6.0)

    def test_percentile_exact_over_window(self):
        agg = TimeSeriesAggregator()
        for i, v in enumerate([5.0, 1.0, 9.0, 3.0, 7.0]):
            agg.observe("lat", v, kind="value", ts=10.0 + i)
        assert agg.window_percentile("lat", 50, window_s=60.0,
                                     now=15.0) == 5.0
        assert agg.window_percentile("lat", 100, window_s=60.0,
                                     now=15.0) == 9.0
        with pytest.raises(ValueError):
            agg.window_percentile("lat", 101)

    def test_unknown_series_and_kind_validation(self):
        agg = TimeSeriesAggregator()
        assert agg.window_rate("nope") == 0.0
        assert agg.latest("nope") is None
        with pytest.raises(ValueError):
            agg.observe("x", 1.0, kind="bogus")

    # -- ISSUE 12 regression: a subset-tag query used to hit only the
    # exact (name, tags) key, so per-(deployment, pool) LLM gauges queried
    # by pool alone returned 0.0 (last-writer-wins on the miss path).
    def test_subset_tag_query_rolls_up_gauges(self):
        agg = TimeSeriesAggregator()
        for i in range(5):
            ts = 100.0 + i
            agg.observe("kv_in_use", 10.0, {"pool": "prefill", "node": "a"},
                        kind="gauge", ts=ts)
            agg.observe("kv_in_use", 30.0, {"pool": "decode", "node": "a"},
                        kind="gauge", ts=ts)
        # Exact-series query is untouched by the rollup path.
        assert agg.window_rate(
            "kv_in_use", {"pool": "prefill", "node": "a"},
            window_s=10.0, now=104.0) == pytest.approx(10.0)
        # Subset query averages gauge levels across matching tag-sets.
        assert agg.window_rate("kv_in_use", {"pool": "decode"},
                               window_s=10.0, now=104.0) == pytest.approx(30.0)
        assert agg.window_rate("kv_in_use", window_s=10.0,
                               now=104.0) == pytest.approx(20.0)
        # Mismatched tag value still matches nothing.
        assert agg.window_rate("kv_in_use", {"pool": "frontend"},
                               window_s=10.0, now=104.0) == 0.0

    def test_subset_tag_query_sums_counter_rates(self):
        agg = TimeSeriesAggregator()
        for i in range(4):
            ts = 100.0 + 10.0 * i
            agg.observe("tok_total", 30.0 * i, {"pool": "p1"},
                        kind="counter", ts=ts)
            agg.observe("tok_total", 60.0 * i, {"pool": "p2"},
                        kind="counter", ts=ts)
        # p1: 90 tokens / 30 s, p2: 180 / 30 s -> pooled 9/s.
        assert agg.window_rate("tok_total", window_s=30.0,
                               now=130.0) == pytest.approx(9.0)
        assert agg.window_sum("tok_total", window_s=30.0,
                              now=130.0) == pytest.approx(270.0)

    def test_window_values_and_percentile_pool_across_tag_sets(self):
        agg = TimeSeriesAggregator()
        agg.observe("ttft", 0.1, {"deployment": "d", "pool": "p1"},
                    kind="value", ts=100.0)
        agg.observe("ttft", 0.3, {"deployment": "d", "pool": "p2"},
                    kind="value", ts=101.0)
        agg.observe("ttft", 0.9, {"deployment": "other", "pool": "p1"},
                    kind="value", ts=102.0)
        vals = agg.window_values("ttft", {"deployment": "d"},
                                 window_s=60.0, now=102.0)
        assert sorted(vals) == [0.1, 0.3]
        assert agg.window_percentile("ttft", 99, tags={"deployment": "d"},
                                     window_s=60.0, now=102.0) == 0.3
        # latest() stays exact-match only: no single meaningful value
        # exists across tag-sets.
        assert agg.latest("ttft", {"deployment": "d"}) is None

    def test_retention_prunes_but_keeps_baseline(self):
        agg = TimeSeriesAggregator(max_window_s=50.0)
        for i in range(20):
            agg.observe("c", float(i), kind="counter", ts=10.0 * i)
        series = agg._get("c", None)
        assert series.ts[0] < series.ts[-1] - 50.0 or len(series.ts) <= 2
        # The rate over the full retention window is still well-defined.
        assert agg.window_rate("c", window_s=50.0, now=190.0) > 0.0

    def test_sample_registry_ingests_counters(self):
        c = um.Counter("test_tsagg_sampled_total", "sampled by the window")
        agg = TimeSeriesAggregator()
        c.inc(4)
        agg.sample_registry(ts=500.0)
        c.inc(8)
        n = agg.sample_registry(ts=510.0)
        assert n > 0
        assert agg.window_rate("test_tsagg_sampled_total", window_s=10.0,
                               now=510.0) == pytest.approx(0.8)

    def test_snapshot_merge_and_collector_cluster_rate(self):
        def node(offset):
            a = TimeSeriesAggregator()
            for i in range(4):
                a.observe("req_total", offset * i, {"d": "a"},
                          kind="counter", ts=100.0 + 10.0 * i)
            return a

        col = TimeSeriesCollector()
        col.push(node(30.0).snapshot(), source="n1")  # 3/s
        col.push(node(70.0).snapshot(), source="n2")  # 7/s
        cluster = col.window_rate("req_total", {"d": "a"}, window_s=30.0,
                                  now=130.0)
        assert cluster == pytest.approx(10.0)
        one = col.window_rate("req_total", {"d": "a", "node": "n2"},
                              window_s=30.0, now=130.0)
        assert one == pytest.approx(7.0)

    def test_openmetrics_text_shape(self):
        agg = TimeSeriesAggregator()
        agg.observe("m_total", 5.0, {"k": "v"}, kind="counter", ts=100.0)
        agg.observe("m_total", 11.0, {"k": "v"}, kind="counter", ts=130.0)
        text = agg.openmetrics_text(windows=(60.0,), now=160.0)
        assert text.endswith("# EOF\n")
        assert '# TYPE m_total_last gauge' in text
        assert 'm_total_last{k="v"} 11' in text
        assert 'm_total_roll{k="v",window_s="60"} 0.1' in text

    def test_serve_request_rate_query(self):
        from ray_tpu.serve import metrics as serve_metrics

        dep = "tsagg-rate-dep"
        serve_metrics.REQUESTS_TOTAL.inc(3, {"deployment": dep})
        rate = serve_metrics.request_rate(dep, window_s=60.0)
        assert rate >= 0.0  # cold start: defined, not an error
        serve_metrics.REQUESTS_TOTAL.inc(6, {"deployment": dep})
        assert serve_metrics.request_rate(dep, window_s=60.0) >= rate


# --------------------------------------------------------------------------
# Run registry + list_train_runs state API
# --------------------------------------------------------------------------
class TestRunRegistry:
    def setup_method(self):
        run_registry.clear()

    def teardown_method(self):
        run_registry.clear()

    def test_lifecycle_and_state_api(self):
        run_registry.register_run("r1", world_size=4, target_world=4,
                                  path="/tmp/r1", elastic=True)
        run_registry.update_run("r1", world_size=3, last_committed_step=17)
        run_registry.record_event("r1", {"type": "shrink", "from_world": 4,
                                         "to_world": 3})
        rows = state_api.list_train_runs()
        (row,) = [r for r in rows if r["name"] == "r1"]
        assert row["status"] == "running"
        assert row["world_size"] == 3 and row["target_world"] == 4
        assert row["last_committed_step"] == 17
        assert row["events"][0]["type"] == "shrink"
        run_registry.finish_run("r1", "finished")
        assert state_api.get_train_run("r1")["status"] == "finished"
        assert state_api.list_train_runs(
            filters=[("status", "=", "running")]) == []

    def test_copies_do_not_leak_live_rows(self):
        run_registry.register_run("r2", world_size=2, target_world=2)
        row = run_registry.get_run("r2")
        row["world_size"] = 99
        row["events"].append({"type": "bogus"})
        fresh = run_registry.get_run("r2")
        assert fresh["world_size"] == 2 and fresh["events"] == []

    def test_unknown_name_update_is_noop(self):
        run_registry.update_run("ghost", world_size=1)
        run_registry.record_event("ghost", {"type": "x"})
        run_registry.finish_run("ghost", "failed")
        assert run_registry.get_run("ghost") is None

    def test_events_and_finished_rows_bounded(self):
        run_registry.register_run("big", world_size=1, target_world=1)
        for i in range(run_registry._MAX_EVENTS + 10):
            run_registry.record_event("big", {"type": "shrink", "i": i})
        evs = run_registry.get_run("big")["events"]
        assert len(evs) == run_registry._MAX_EVENTS
        assert evs[-1]["i"] == run_registry._MAX_EVENTS + 9  # newest kept

        for i in range(run_registry._MAX_FINISHED + 8):
            run_registry.register_run(f"f{i}", world_size=1, target_world=1)
            run_registry.finish_run(f"f{i}", "finished")
        done = [r for r in run_registry.list_runs()
                if r["status"] != "running"]
        assert len(done) <= run_registry._MAX_FINISHED
        assert run_registry.get_run("big") is not None, "live row evicted"


# --------------------------------------------------------------------------
# Timeline fusion: elastic fit() with tracing on -> one "train" lane
# --------------------------------------------------------------------------
def _profiled_loop(config):
    import jax.numpy as jnp

    from ray_tpu import collective, train

    ctx = train.get_context()
    shard = train.get_dataset_shard("train")
    ckpt = train.get_checkpoint()
    step = int(ckpt.to_pytree()["step"]) if ckpt is not None else -1
    w = float(ckpt.to_pytree()["w"]) if ckpt is not None else 0.0
    while True:
        batch = shard.next_batch(config.get("batch", 2))
        n = 0 if batch is None else len(batch[0])
        contrib = 0.0 if batch is None else float(np.sum(batch[1]))
        vec = np.asarray(collective.allreduce(
            jnp.asarray([float(n), contrib]),
            group_name=ctx.collective_group))
        if vec[0] == 0:
            break
        w += float(vec[1])
        step += 1
        train.report({"step": step, "w": w, "world": ctx.world_size},
                     checkpoint={"w": jnp.asarray(np.float64(w)),
                                 "step": jnp.asarray(np.int64(step))})
        time.sleep(0.05)


@pytest.fixture
def elastic_cluster():
    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 0})
    nodes = [cluster.add_node(num_cpus=1) for _ in range(3)]
    yield cluster, nodes
    ray_tpu.shutdown()


def test_timeline_fuses_train_elastic_and_checkpoint(elastic_cluster,
                                                     tmp_path):
    """One elastic shrink→grow run with tracing on: the exported Perfetto
    trace must show steps, their wait buckets, the elastic recovery, and
    checkpoint phases in the shared "train" process lane, and
    list_train_runs() must track the run live and after."""
    cluster, nodes = elastic_cluster
    run_registry.clear()
    tracing.clear_spans()
    tracing.enable_tracing()
    try:
        data = np.arange(1, 361, dtype=np.float64)
        trainer = JaxTrainer(
            _profiled_loop,
            scaling_config=ScalingConfig(
                num_workers=3, worker_mode="threads",
                elastic=ElasticConfig(min_workers=1,
                                      grow_check_period_s=0.3)),
            datasets={"train": data},
            run_config=RunConfig(
                name="fusion", storage_path=str(tmp_path),
                checkpoint_config=CheckpointConfig(async_save=True,
                                                   replica_memory_steps=2),
                failure_config=FailureConfig(max_failures=3)))
        box = {}
        t = threading.Thread(
            target=lambda: box.update(result=trainer.fit()), daemon=True)
        t.start()

        # The state API sees the run live, at the full world.
        deadline = time.time() + 20
        live = None
        while time.time() < deadline:
            rows = [r for r in state_api.list_train_runs(
                filters=[("status", "=", "running")])
                if r["name"] == "fusion"]
            if rows and rows[0]["world_size"] == 3:
                live = rows[0]
                break
            time.sleep(0.05)
        assert live is not None, "running row never appeared"
        assert live["elastic"] is True and live["target_world"] == 3

        time.sleep(1.0)
        assert simulate_preemption(str(nodes[0])) is not None
        time.sleep(1.5)
        cluster.add_node(num_cpus=1)
        t.join(timeout=120)
        assert not t.is_alive(), "fit() hung"
        r = box["result"]
        assert r.error is None, r.error
        kinds = [e["type"] for e in r.elastic_events]
        assert "shrink" in kinds, r.elastic_events

        # Final registry row: finished, committed progress, events recorded.
        row = state_api.get_train_run("fusion")
        assert row["status"] == "finished"
        assert row["last_committed_step"] is not None
        assert row["last_committed_step"] >= 0
        assert [e["type"] for e in row["events"]] == kinds

        out = tmp_path / "fusion_timeline.json"
        events = ray_tpu.timeline(str(out))
        loaded = json.load(open(out))  # valid Perfetto/chrome JSON
        assert loaded and isinstance(loaded, list)
        for ev in loaded:
            assert ev["ph"] in ("X", "i")
            assert "pid" in ev and "tid" in ev and "ts" in ev
        train_lane = [ev for ev in events if ev.get("pid") == "train"]
        names = {ev["name"] for ev in train_lane}
        assert "train.step" in names, sorted(names)
        assert "train.data_wait" in names, sorted(names)
        assert "train.elastic" in names, sorted(names)
        assert any(n.startswith("checkpoint.") for n in names), sorted(names)
        # Wait buckets nest under their step spans.
        steps = {ev["args"]["span_id"] for ev in train_lane
                 if ev["name"] == "train.step"}
        waits = [ev for ev in train_lane if ev["name"] == "train.data_wait"]
        assert waits and all(ev["args"]["parent_id"] in steps
                             for ev in waits)
        # The elastic recovery span carries the shrink's shape.
        rec = next(ev for ev in train_lane if ev["name"] == "train.elastic")
        assert rec["args"]["from_world"] == 3
        assert rec["args"]["to_world"] == 2
    finally:
        tracing.disable_tracing()
        tracing.clear_spans()
        run_registry.clear()


# --------------------------------------------------------------------------
# Agent HTTP routes: /timeseries + /api/train_runs
# --------------------------------------------------------------------------
def test_agent_serves_timeseries_and_train_runs():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        @ray_tpu.remote
        def work(x):
            return x * 2

        assert ray_tpu.get(work.remote(21)) == 42

        from ray_tpu._private.metrics_agent import MetricsAgent
        from ray_tpu._private.runtime import get_runtime

        run_registry.clear()
        run_registry.register_run("http-run", world_size=2, target_world=2)
        run_registry.update_run("http-run", last_committed_step=5)
        agent = MetricsAgent(get_runtime())
        try:
            base = f"http://127.0.0.1:{agent.port}"
            req = urllib.request.urlopen(f"{base}/timeseries", timeout=5)
            assert "openmetrics" in req.headers.get("Content-Type", "")
            body = req.read().decode()
            assert body.endswith("# EOF\n")
            assert "ray_tpu_tasks_finished_total_last" in body

            runs = json.load(urllib.request.urlopen(
                f"{base}/api/train_runs", timeout=5))
            (row,) = [r for r in runs if r["name"] == "http-run"]
            assert row["status"] == "running"
            assert row["last_committed_step"] == 5
        finally:
            agent.stop()
    finally:
        run_registry.clear()
        ray_tpu.shutdown()
