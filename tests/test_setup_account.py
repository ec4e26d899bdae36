"""The set-up's account from inside the program (docs/observability.md §
Time to first step, § Compile registry):

* a compile is recorded in its phases: nested jits' trace time counts once,
  the phases of a labelled first call sum to its seconds, the persistent
  cache's answer and its load are on the record, an event on a thread
  without a label changes nothing;
* ``device_telemetry.setup_account()``: rows in order through a tiny
  ``JaxTrainer.fit()``, closed once, ``spanned_s + unspanned_s ==
  to_first_step_s``; the gaps by the rows they lie between;
* the three spans of the set-up are registered, ``train.compute`` is not.
"""

import functools
import logging
import threading
import time

import numpy as np
import pytest

from ray_tpu.train import profiler as train_profiler
from ray_tpu.util import device_telemetry as dt
from ray_tpu.util import tracing

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(autouse=True)
def clean_telemetry():
    train_profiler.activate(None)  # a neighbour's, left on this thread
    dt.reset()
    yield
    dt.reset()
    train_profiler.activate(None)


# ------------------------------------------------------ a compile's phases
def test_nested_traces_count_once_by_hand():
    """Spans as jax fires them for a jitted ``f`` that calls a jitted
    ``inner`` twice: innermost first, each inside the outer one's."""
    with dt.compile_label("f") as label:
        dt._on_time_span(TRACE, 10.1, 10.2)   # inner
        dt._on_time_span(TRACE, 10.3, 10.5)   # inner again
        dt._on_time_span(TRACE, 10.0, 11.0)   # f holds both
        dt._on_time_span(LOWER, 11.0, 11.25)
        dt._on_time_span(COMPILE, 11.25, 12.0)
    assert label.trace_s == pytest.approx(1.0)  # not 1.3
    assert label.lower_s == pytest.approx(0.25)
    assert label._spans == [(10.0, 1.0), (11.0, 0.25), (11.25, 0.75)]


def test_a_compile_inside_a_trace_is_not_traced_time():
    """An eager operation while ``f`` is traced (its own trace, lowering
    and compile, then ``f``'s trace span around all three): every second
    belongs to one phase."""
    dt.listen_for_compiles()
    with dt.compile_label("f") as label:
        dt._on_time_span(TRACE, 1.0, 1.1)
        dt._on_time_span(LOWER, 1.1, 1.2)
        dt._on_duration(COMPILE, 0.5)
        dt._on_time_span(COMPILE, 1.2, 1.7)
        dt._on_time_span(TRACE, 0.0, 2.0)
        dt._on_time_span(LOWER, 2.0, 2.5)
        dt._on_duration(COMPILE, 1.0)
        dt._on_time_span(COMPILE, 2.5, 3.5)
    phases = label.phases(4.0)
    assert phases["trace_s"] == pytest.approx(0.1 + 1.3)
    assert phases["lower_s"] == pytest.approx(0.1 + 0.5)
    assert phases["compile_s"] == pytest.approx(1.5)
    assert phases["other_s"] == pytest.approx(0.5)
    first, second = dt.compile_records("f")
    assert (first["trace_s"], first["lower_s"]) == (0.1, 0.1)
    assert (second["trace_s"], second["lower_s"]) == (1.3, 0.5)


def test_an_event_on_a_thread_without_a_label_changes_nothing():
    dt.listen_for_compiles()
    before = (dt.compile_records(), dt.first_calls(), dt.setup_account())
    seen = []

    def other_thread():
        dt._on_time_span(TRACE, 0.0, 5.0)
        dt._on_time_span(LOWER, 5.0, 6.0)
        dt._on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                        1.0)
        seen.append(getattr(dt._thread, "label", None))

    with dt.compile_label("here") as label:
        thread = threading.Thread(target=other_thread)
        thread.start()
        thread.join(timeout=10)
    assert seen == [None] and not thread.is_alive()
    assert (label.trace_s, label.lower_s, label._spans) == (0.0, 0.0, None)
    assert (dt.compile_records(), dt.first_calls(),
            dt.setup_account()) == before


def test_nested_jits_trace_time_is_counted_once():
    import jax
    import jax.numpy as jnp

    dt.listen_for_compiles()
    spans = []

    def listener(event, start, end, **_):
        spans.append((event, start, end))

    inner = jax.jit(lambda x: jnp.sin(x) * 2.0)

    @jax.jit
    def f(x):
        for _ in range(3):
            x = inner(x)
        return jnp.cos(x).sum()

    x = np.ones(8, np.float32)
    jax.monitoring.register_event_time_span_listener(listener)
    try:
        t0 = time.perf_counter()
        with dt.compile_label("f") as label:
            f(x)
        seconds = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_time_span_listener(listener)
    traces = [(s, e) for event, s, e in spans if event == TRACE]
    outer = max(traces, key=lambda t: t[1] - t[0])
    held = [t for t in traces if t is not outer
            and outer[0] <= t[0] and t[1] <= outer[1]]
    assert len(held) >= 3  # inner (once a call that traced), cos, sum
    # once: no more than the wall, and more than the outer less the inner
    assert label.trace_s <= seconds
    assert label.trace_s > (outer[1] - outer[0]) - sum(
        e - s for s, e in held) - 1e-9
    assert label.trace_s == pytest.approx(outer[1] - outer[0], abs=1e-6)
    phases = label.phases(seconds)
    assert phases["lower_s"] > 0 and phases["other_s"] >= 0
    assert sum(phases[k] for k in ("trace_s", "lower_s", "compile_s",
                                   "other_s")) == pytest.approx(seconds,
                                                                abs=1e-5)
    (record,) = dt.compile_records("f")
    assert record["trace_s"] == pytest.approx(label.trace_s, abs=1e-6)
    assert record["lower_s"] == pytest.approx(label.lower_s, abs=1e-6)


def _tiny_step():
    import jax

    from ray_tpu.models import llama
    from ray_tpu.parallel.train_state import jit_train_step

    config = llama.LlamaConfig.tiny()
    optimizer = llama.make_optimizer()
    params = llama.init_params(config, jax.random.key(0))
    opt_state = optimizer.init(params)
    step = jit_train_step(llama.make_train_step(config, optimizer))
    tokens = np.zeros((2, config.seq_len), np.int32)
    return step, params, opt_state, tokens


def test_a_labelled_first_call_says_where_it_went():
    step, params, opt_state, tokens = _tiny_step()
    params, opt_state, _ = step(params, opt_state, tokens, tokens)
    (call,) = dt.first_calls("train_step")
    assert call["trace_s"] > 0 and call["lower_s"] > 0
    assert call["compile_s"] > 0 and call["other_s"] >= 0
    assert call["trace_s"] + call["lower_s"] + call["compile_s"] \
        + call["other_s"] == pytest.approx(call["seconds"], abs=1e-5)
    assert call["cache"] in ("hit", "miss", None)
    assert call["cache_load_s"] <= call["compile_s"]
    (record,) = dt.compile_records("train_step")
    assert record["trace_s"] == pytest.approx(call["trace_s"], abs=1e-5)
    # the account's row is the same span
    (row,) = [r for r in dt.setup_account()["rows"]
              if r["name"] == "train.first_call"]
    assert row["label"] == "train_step"
    assert row["end"] - row["start"] == pytest.approx(call["seconds"])
    assert row["trace_s"] == call["trace_s"] and "remat_kept" not in row
    # a second call leaves no record and closes the account
    assert dt.setup_account()["closed"] is None
    step(params, opt_state, tokens, tokens)
    assert len(dt.first_calls("train_step")) == 1
    assert dt.setup_account()["closed"]["by"] == "host"


def test_the_persistent_cache_answers_miss_then_hit(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    dt.listen_for_compiles()
    settings = {"jax_compilation_cache_dir": str(tmp_path),
                "jax_persistent_cache_min_compile_time_secs": 0.0,
                "jax_persistent_cache_min_entry_size_bytes": -1,
                "jax_enable_compilation_cache": True}
    before = {k: getattr(jax.config, k) for k in settings}
    for k, v in settings.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()

    def program(x):
        return jnp.tanh(x @ x.T).sum() * 54.0  # this test's alone

    x = np.ones((16, 16), np.float32)
    labels = []
    try:
        # one call site: where the key holds the HLO's metadata
        # (``configure_compile_cache``), another line is another program
        for _ in range(2):
            with dt.compile_label("cached") as label:
                jax.jit(program)(x)
            labels.append(label)
            jax.clear_caches()
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    first, second = labels
    assert (first.cache, first.misses, first.cache_load_s) == ("miss", 1, 0.0)
    assert (second.cache, second.hits) == ("hit", 1)
    assert 0 < second.cache_load_s <= second.compile_s
    miss, hit = dt.compile_records("cached")
    assert (miss["cache"], miss["cache_load_s"]) == ("miss", 0.0)
    assert hit["cache"] == "hit" and hit["cache_load_s"] > 0
    assert hit["trace_s"] > 0 and hit["lower_s"] > 0  # a hit still traces
    assert second.phases(1.0)["cache"] == "hit"


def test_xla_compile_span_covers_trace_to_executable():
    import jax
    import jax.numpy as jnp

    dt.listen_for_compiles()
    tracing.clear_spans()
    tracing.enable_tracing()
    try:
        t0 = time.time()
        with dt.compile_label("spanned"):
            jax.jit(lambda x: jnp.exp(x) * 54.5)(np.ones(4, np.float32))
        (span,) = [s for s in tracing.exported_spans()
                   if s["name"] == "xla.compile"
                   and s["attributes"]["label"] == "spanned"]
    finally:
        tracing.disable_tracing()
        tracing.clear_spans()
    attrs = span["attributes"]
    assert attrs["trace_s"] > 0 and attrs["lower_s"] > 0
    assert t0 <= span["start"] and span["end"] - span["start"] >= \
        attrs["trace_s"] + attrs["lower_s"] + attrs["compile_s"] - 1e-6


# ------------------------------------------------------------ the account
def test_account_arithmetic_by_hand():
    start, source = dt._process_start()
    assert source in ("proc", "import") and start <= time.time()
    with dt._lock:  # a process start this test can count from
        saved, dt._process_started = dt._process_started, (1000.0, "proc")
    try:
        dt.record_setup_span("runtime.init", 1002.0, 1003.0, {})
        dt.record_setup_span("train.fit_setup", 1003.5, 1004.0,
                             {"workers": 1, "worker_mode": "threads",
                              "not_kept": object()})
        # nested in the one before it, and one overlapping its end
        dt.record_setup_span("train.init_params", 1003.6, 1003.9, {})
        dt.record_setup_span("train.first_call", 1003.9, 1006.0,
                             {"label": "train_step"})
        open_account = dt.setup_account()
        assert open_account["to_first_step_s"] is None
        assert open_account["unspanned_s"] is None
        assert open_account["spanned_s"] == pytest.approx(3.5)
        closed = dt.close_setup_account(1007.0, by="device")
        assert dt.close_setup_account(1009.0) is None  # once
        dt.record_setup_span("train.first_call", 1008.0, 1008.5, {})  # late
        assert dt.setup_account() == closed
    finally:
        with dt._lock:
            dt._process_started = saved
    assert (closed["start"], closed["start_from"]) == (1000.0, "proc")
    assert [r["name"] for r in closed["rows"]] == [
        "runtime.init", "train.fit_setup", "train.init_params",
        "train.first_call"]
    assert closed["rows"][1] == {"name": "train.fit_setup", "start": 1003.5,
                                 "end": 1004.0, "workers": 1,
                                 "worker_mode": "threads"}
    assert closed["closed"] == {"ts": 1007.0, "by": "device"}
    assert closed["to_first_step_s"] == pytest.approx(7.0)
    assert closed["spanned_s"] == pytest.approx(1.0 + 2.5)
    assert closed["unspanned_s"] == pytest.approx(3.5)
    assert closed["gaps"] == [
        {"after": "process_start", "before": "runtime.init", "seconds": 2.0},
        {"after": "runtime.init", "before": "train.fit_setup",
         "seconds": 0.5},
        {"after": "train.first_call", "before": "first_step",
         "seconds": 1.0}]
    assert dt.TIME_TO_FIRST_STEP.get() == pytest.approx(7.0)


def test_setup_span_is_a_span_a_row_and_ends_once():
    tracing.clear_spans()
    tracing.enable_tracing()
    try:
        span = dt.setup_span("train.fit_setup", {"workers": 2})
        with span as attributes:
            attributes["worker_mode"] = "threads"
            span.end()       # the function that knows ends it early
            time.sleep(0.01)
        names = [s["name"] for s in tracing.exported_spans()]
    finally:
        tracing.disable_tracing()
        tracing.clear_spans()
    assert names == ["train.fit_setup"]
    (row,) = dt.setup_account()["rows"]
    assert row["workers"] == 2 and row["worker_mode"] == "threads"
    assert row["end"] - row["start"] < 0.01


def test_fit_leaves_an_account_in_order(caplog):
    """A tiny ``JaxTrainer.fit()``: the rows in the order the set-up runs
    them, closed once by the step profiler's resolver thread, the two
    shares summing to the whole."""
    import jax

    import ray_tpu
    from ray_tpu import data, train
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh
    from ray_tpu.parallel.train_state import (create_sharded_state,
                                              jit_train_step)

    config = llama.LlamaConfig.tiny()

    def loop():
        mesh = make_mesh(MeshSpec(data=1), jax.devices()[:1])
        optimizer = llama.make_optimizer()
        params, opt_state = create_sharded_state(
            functools.partial(llama.init_params, config),
            llama.logical_axes(config), mesh, jax.random.key(0), optimizer)
        step = jit_train_step(llama.make_train_step(config, optimizer),
                              mesh=mesh)
        batches = train.get_dataset_shard("train").iter_batches(
            batch_size=2, device_sharding=batch_sharding(mesh))
        for i, batch in enumerate(batches):
            params, opt_state, loss = step(
                params, opt_state, batch["tokens"], batch["targets"])
            train.report({"step": i, "loss": loss})
        list(train.active_profiler().history)  # waits for the resolver

    rows = np.random.default_rng(0).integers(
        0, config.vocab_size, (8, config.seq_len + 1)).astype(np.int32)
    dataset = data.from_items(
        [{"tokens": r[:-1], "targets": r[1:]} for r in rows])
    ray_tpu.shutdown()  # a neighbour's runtime: this one times its own
    dt.reset()
    t0 = time.time()
    with caplog.at_level(logging.INFO, logger="ray_tpu.train"):
        ray_tpu.init(num_cpus=2)
        try:
            result = train.JaxTrainer(
                loop,
                scaling_config=train.ScalingConfig(num_workers=1,
                                                   worker_mode="threads"),
                datasets={"train": dataset}).fit()
        finally:
            ray_tpu.shutdown()
    assert result.error is None, result.error

    account = dt.setup_account()
    names = [r["name"] for r in account["rows"]]
    assert names[:4] == ["runtime.init", "train.fit_setup",
                         "train.init_params", "train.init_opt_state"]
    assert sorted(names[4:]) == ["train.first_batch", "train.first_call"]
    by_name = {r["name"]: r for r in account["rows"]}
    assert all(t0 <= r["start"] <= r["end"] for r in account["rows"])
    assert by_name["train.fit_setup"]["workers"] == 1
    assert by_name["train.fit_setup"]["worker_mode"] == "threads"
    # fit()'s set-up ended as the loop function was entered: the
    # controller thread sees that a wake-up after the worker said it, so
    # the loop's first program may have started, and has not ended
    assert by_name["train.fit_setup"]["end"] \
        <= by_name["train.init_params"]["end"]
    assert by_name["train.first_batch"]["bytes"] == 2 * 2 * 4 * config.seq_len
    calls = {c["label"]: c for c in dt.first_calls()}
    assert set(calls) == {"init_params", "init_opt_state", "train_step"}
    for name, label in (("train.init_params", "init_params"),
                        ("train.init_opt_state", "init_opt_state"),
                        ("train.first_call", "train_step")):
        row, seconds = by_name[name], calls[label]["seconds"]
        assert row["trace_s"] > 0 and row["other_s"] >= 0, row
        # the phases part the call's own seconds; the span around the call
        # is no shorter (two clocks, and a thread may wait between them)
        assert row["trace_s"] + row["lower_s"] + row["compile_s"] \
            + row["other_s"] == pytest.approx(seconds, abs=1e-5)
        assert row["end"] - row["start"] >= seconds - 1e-3
    # closed once, by the device's answer, after the first call's end
    assert account["closed"]["by"] == "device"
    assert account["closed"]["ts"] >= by_name["train.first_call"]["end"]
    assert account["spanned_s"] + account["unspanned_s"] \
        == pytest.approx(account["to_first_step_s"], abs=1e-5)
    assert account["unspanned_s"] == pytest.approx(
        sum(g["seconds"] for g in account["gaps"]), abs=1e-5)
    assert account["gaps"][0]["after"] == "process_start"
    assert account["gaps"][-1]["before"] == "first_step"
    assert dt.TIME_TO_FIRST_STEP.get() == account["to_first_step_s"]
    said = [r.getMessage() for r in caplog.records
            if r.name == "ray_tpu.train" and "first steady step" in
            r.getMessage()]
    assert len(said) == 1 and "train.first_call" in said[0]


# ------------------------------------------------------------ the registry
def test_the_registry_holds_the_setups_spans_and_no_residual():
    import os

    from ray_tpu.devtools.analysis import core

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ctx = core.AnalysisContext(root=repo)
    core.load_registries(ctx, os.path.join(repo, "ray_tpu"))
    for name in ("runtime.init", "train.fit_setup", "train.first_batch"):
        assert name in tracing.SPAN_REGISTRY and name in ctx.span_names
    assert "train.compute" not in tracing.SPAN_REGISTRY
    assert "train.compute" not in ctx.span_names
