"""What a step's row says of the device (PR 36): the step counter that leaves
the compiled step through a ref (``moe_rows``), the late device facts of the
``StepProfiler`` rows (``done``, ``device_period``, ``in_flight``), and the
static check on counter names.

The row tests drive ``StepProfiler.dispatched`` with sentinels whose
readiness the test decides; the plumbing tests run a tiny expert model
through ``jit_train_step`` on one CPU device.
"""

import functools
import os
import re
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, moe
from ray_tpu.parallel.train_state import jit_train_step
from ray_tpu.train import StepProfiler
from ray_tpu.train import metrics as train_metrics
from ray_tpu.train import profiler as train_profiler
from ray_tpu.util import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _state(config, batch=2):
    """(optimizer, committed params, opt_state, tokens) on one device."""
    optimizer = llama.make_optimizer()
    device = jax.devices()[0]
    params = jax.device_put(llama.init_params(config, jax.random.key(0)),
                            device)
    opt_state = jax.device_put(optimizer.init(params), device)
    tokens = jax.device_put(jax.random.randint(
        jax.random.key(1), (batch, config.seq_len), 0,
        config.vocab_size - 1), device)
    return optimizer, params, opt_state, tokens


@pytest.fixture
def profiler():
    p = StepProfiler(run_name="counters", rank=0)
    train_profiler.activate(p)
    yield p
    train_profiler.activate(None)


def _resolver_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("train-step-done")]


# ------------------------------------------------ the counter, at the layer
@pytest.mark.parametrize("first,held", [(0, 8), (2, 2), (6, 2)])
def test_the_layer_counts_the_rows_of_the_experts_it_holds(first, held):
    """``moe_mlp``'s third output against a numpy recount of ``route``'s
    ids: every expert held, it sums to N x k; with a share, the slice."""
    k, n_experts, d, width = 2, 8, 32, 16
    keys = jax.random.split(jax.random.key(3), 5)
    h = jax.random.normal(keys[0], (2, 24, d))
    blk = {"router": jax.random.normal(keys[1], (d, n_experts)),
           "w_gate": jax.random.normal(keys[2], (held, d, width)) * 0.1,
           "w_up": jax.random.normal(keys[3], (held, d, width)) * 0.1,
           "w_down": jax.random.normal(keys[4], (held, width, d)) * 0.1}
    _, _, counts = jax.jit(functools.partial(
        moe.moe_mlp, experts_per_token=k, norm_topk_prob=True,
        dtype=jnp.float32, first_held=first))(h, blk)
    rows = counts["moe_rows"]
    _, experts, _ = moe.route(h.reshape(-1, d), blk["router"], k, True)
    recount = np.bincount(np.asarray(experts).ravel(), minlength=n_experts)
    assert rows.dtype == jnp.int32 and rows.shape == (1, held)
    np.testing.assert_array_equal(np.asarray(rows)[0],
                                  recount[first:first + held])
    if held == n_experts:
        assert int(rows.sum()) == 2 * 24 * k


# ----------------------------------------------- the counter, out of the step
@pytest.mark.parametrize("preset", ["tiny_moe", "tiny_sdar"])
def test_three_outputs_and_the_rows_carry_moe_rows(preset, profiler):
    config = getattr(llama.LlamaConfig, preset)()
    optimizer, params, opt_state, tokens = _state(config)
    step = jit_train_step(llama.make_train_step(config, optimizer))
    # what the step will count, from the same parameters, before they are
    # donated
    want = np.asarray(jax.jit(functools.partial(
        llama.loss_and_counters, config=config))(
            params, tokens, tokens)[1]["moe_rows"])
    out = step(params, opt_state, tokens, tokens)
    assert len(out) == 3 and out[2].shape == ()
    profiler.step_boundary()
    row = profiler.history[-1]
    got = np.asarray(row["moe_rows"])
    held = len(config.held)
    assert got.shape == (config.n_layer, 1, held)
    np.testing.assert_array_equal(got, want)
    pairs = tokens.shape[0] * tokens.shape[1] * config.experts_per_token \
        * (2 if config.block_length else 1)
    per_layer = got.sum(axis=(1, 2))
    if held == config.n_experts:
        assert per_layer.tolist() == [pairs] * config.n_layer
        assert "moe_moved" not in row  # every pair, on every step: no count
    else:
        assert (per_layer < pairs).all()
        # a share: the windows each layer's own count needed, no more
        moved = np.asarray(row["moe_moved"])
        assert moved.shape == (config.n_layer, 1)
        window = moe.window_rows(pairs)
        assert (moved[:, 0] == -(-per_layer // window) * window).all()
        assert train_metrics.MOE_MOVED_ROWS.get() == pytest.approx(
            moved.mean())
    assert row["in_flight"] == 0 and row["done"] > 0


@pytest.mark.parametrize("preset", ["tiny_moe", "tiny_sdar"])
def test_under_a_mesh_the_ref_holds_a_row_for_each_shard(preset, profiler):
    """2 x 2 devices, the batch split four ways: the ref is replicated over
    the mesh, the second call is the first one's program, and each shard's
    rows stay apart; where the layers hold a share each shard walks the
    windows its own count needs."""
    from ray_tpu.parallel.mesh import MeshSpec, batch_sharding, make_mesh
    from ray_tpu.parallel.train_state import create_sharded_state

    config = getattr(llama.LlamaConfig, preset)()
    mesh = make_mesh(MeshSpec(data=2, fsdp=2), jax.devices()[:4])
    optimizer = llama.make_optimizer()
    params, opt_state = create_sharded_state(
        functools.partial(llama.init_params, config),
        llama.logical_axes(config), mesh, jax.random.key(0), optimizer)
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (4, config.seq_len), 0,
                           config.vocab_size - 1), batch_sharding(mesh))
    step = jit_train_step(llama.make_train_step(config, optimizer), mesh=mesh)
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, tokens, tokens)
        profiler.step_boundary()
    first, second = list(profiler.history)
    assert first["compiles"] >= 1 and second["compiles"] == 0
    got = np.asarray(second["moe_rows"])
    assert got.shape == (config.n_layer, 4, len(config.held))
    # a shard is one sequence (with its clean copy under block diffusion):
    # its pairs, in every layer
    pairs = config.seq_len * config.experts_per_token \
        * (2 if config.block_length else 1)
    if len(config.held) == config.n_experts:
        assert (got.sum(axis=2) == pairs).all() and "moe_moved" not in second
        return
    moved = np.asarray(second["moe_moved"])
    window = moe.window_rows(pairs)
    assert moved.shape == (config.n_layer, 4)
    assert (moved == -(-got.sum(axis=2) // window) * window).all()


def _entry_parameters(text: str) -> int:
    entry = text[text.index("\nENTRY "):]
    return len(re.findall(r" parameter\(\d+\)", entry[:entry.index("\n}")]))


def _stripped(text: str, tmp_path, name: str) -> str:
    path = tmp_path / name
    path.write_text(text)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "hlo_strip.py"),
         str(path)], check=True, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu")).stdout


def test_trainstep_runs_the_program_a_plain_jit_gives(tmp_path):
    """The harness compiles ``jax.jit(make_train_step(...))`` itself and
    matches its instruction names against the trace of what ``TrainStep``
    ran: the two must be one program, the counter's parameter included."""
    config = llama.LlamaConfig.tiny_moe()
    optimizer, params, opt_state, tokens = _state(config)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        (params, opt_state, tokens, tokens))
    step = jit_train_step(llama.make_train_step(config, optimizer))
    ours = step._jitted.lower(*abstract).compile().as_text()
    plain = jax.jit(llama.make_train_step(config, optimizer),
                    donate_argnums=(0, 1)).lower(*abstract).compile() \
        .as_text()
    assert _stripped(ours, tmp_path, "ours.txt") \
        == _stripped(plain, tmp_path, "plain.txt")
    # one aliased parameter more than the state and the batch
    assert _entry_parameters(ours) == len(jax.tree.leaves(abstract)) + 1


def test_a_dense_model_makes_no_ref(profiler):
    config = llama.LlamaConfig.tiny()
    optimizer, params, opt_state, tokens = _state(config)
    step_fn = llama.make_train_step(config, optimizer)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        (params, opt_state, tokens, tokens))
    text = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
        *abstract).compile().as_text()
    assert _entry_parameters(text) == len(jax.tree.leaves(abstract))
    step = jit_train_step(step_fn)
    params, opt_state, loss = step(params, opt_state, tokens, tokens)
    assert step_fn.counters == {}
    profiler.step_boundary()
    row = profiler.history[-1]
    # the loss is the sentinel: the device facts without a counter
    assert "moe_rows" not in row
    assert set(train_profiler.DEVICE_KEYS) <= set(row)


def test_a_users_own_step_goes_through_as_before(profiler):
    def own_step(params, opt_state, x):
        return params + x, opt_state, jnp.sum(x)

    step = jit_train_step(own_step)
    params, opt_state = jnp.ones(4), jnp.zeros(4)
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, jnp.ones(4))
        profiler.step_boundary()
    rows = list(profiler.history)
    assert float(loss) == 4.0 and len(rows) == 3
    assert all(r["done"] > 0 and "moe_rows" not in r for r in rows)


def test_without_a_profiler_nothing_is_read_and_no_thread_starts():
    """``RunConfig(profile=False)`` gives the session no profiler: the step
    takes no sentinel and starts no thread."""
    from ray_tpu.train.config import RunConfig

    assert RunConfig(profile=False).profile is False
    train_profiler.activate(None)
    before = set(_resolver_threads())  # an earlier test's may still end
    config = llama.LlamaConfig.tiny_moe()
    optimizer, params, opt_state, tokens = _state(config)
    step_fn = llama.make_train_step(config, optimizer)
    step = jit_train_step(step_fn)
    reads = []
    step._hand_over = lambda *a: reads.append(a)
    params, opt_state, loss = step(params, opt_state, tokens, tokens)
    assert np.isfinite(float(loss)) and reads == []
    assert set(_resolver_threads()) <= before
    # the counter was written all the same: it is part of the program
    assert int(step_fn.counters["moe_rows"][...].sum()) > 0


# ------------------------------------------------------------------ the row
class _Sentinel:
    """Stands for a step's fresh output: ready when the test says so."""

    def __init__(self):
        self._done = threading.Event()

    def finish(self):
        self._done.set()

    def is_ready(self):
        return self._done.is_set()

    def block_until_ready(self):
        assert self._done.wait(timeout=30)
        return self


def test_done_is_monotone_and_the_periods_sum_to_the_window(profiler):
    sentinels = [_Sentinel() for _ in range(6)]
    for s in sentinels:  # the host runs ahead: six dispatched, none done
        profiler.dispatched(s, {})
        profiler.step_boundary()
    t0 = time.perf_counter()
    for s in sentinels:
        time.sleep(0.01)
        s.finish()
    rows = list(profiler.history)  # waits for all six
    t1 = time.perf_counter()
    done = [r["done"] for r in rows]
    assert done == sorted(done) and len(set(done)) == 6
    assert rows[0]["device_period"] is None  # nothing before it
    periods = [r["device_period"] for r in rows[1:]]
    assert all(p > 0 for p in periods)
    assert sum(periods) == pytest.approx(done[-1] - done[0])
    # five periods of ~10 ms inside the fenced stretch, within one step
    assert 0.04 <= sum(periods) <= (t1 - t0)
    # the gauges read the device's clock, not report() to report()
    assert train_metrics.STEP_P50_SECONDS.get() == pytest.approx(
        sorted(periods)[len(periods) // 2])


def test_in_flight_counts_what_the_device_has_not_finished(profiler):
    first = _Sentinel()
    first.finish()
    profiler.dispatched(first, {})
    profiler.step_boundary()
    assert profiler.history[-1]["in_flight"] == 0
    # a fence: everything before is done, so the next finds the queue empty
    flying = [_Sentinel() for _ in range(4)]
    for k, s in enumerate(flying):
        profiler.dispatched(s, {})
        profiler.step_boundary()
        assert len(profiler._outstanding) == k + 1
    for s in flying:
        s.finish()
    rows = list(profiler.history)
    assert [r["in_flight"] for r in rows] == [0, 0, 1, 2, 3]
    after_fence = _Sentinel()
    after_fence.finish()
    profiler.dispatched(after_fence, {})
    profiler.step_boundary()
    assert profiler.history[-1]["in_flight"] == 0
    assert train_metrics.STEPS_IN_FLIGHT.get() == 0


def test_history_read_mid_run_returns_resolved_rows(profiler):
    s = _Sentinel()
    profiler.dispatched(s, {"moe_rows": np.arange(6).reshape(1, 2, 3)})
    profiler.step_boundary()
    # not waiting: the row stands as the host left it
    assert "done" not in profiler.last_attribution()
    threading.Timer(0.05, s.finish).start()
    row = profiler.history[-1]  # waits
    assert row["done"] > 0 and row["moe_rows"] == [[[0, 1, 2], [3, 4, 5]]]
    assert train_metrics.MOE_LOAD_MAX.get() == pytest.approx(
        (2 / 1 + 5 / 4) / 2)
    # a step that dispatched nothing keeps the host's clock and no keys
    profiler.step_boundary()
    assert not set(train_profiler.DEVICE_KEYS) & set(profiler.history[-1])


def test_a_step_of_several_dispatches_is_observed_once(profiler):
    """Gradient accumulation: k ``TrainStep`` calls, one ``report()``.  The
    row sums their periods, and the gauges take that sum once, not a k-th
    of the step k times."""
    warm = _Sentinel()
    warm.finish()
    profiler.dispatched(warm, {})
    profiler.step_boundary()
    profiler.configure(tokens_per_step=1000.0)
    observed = []
    observe = profiler._observe_step
    profiler._observe_step = lambda s: (observed.append(s), observe(s))
    parts = [_Sentinel() for _ in range(3)]
    for s in parts:
        profiler.dispatched(s, {})
    profiler.step_boundary()
    for s in parts:
        time.sleep(0.01)
        s.finish()
    row = profiler.history[-1]
    assert row["device_period"] >= 0.03 and row["in_flight"] == 0
    assert observed == [row["device_period"]]
    assert train_metrics.TOKENS_PER_SECOND.get() == pytest.approx(
        1000.0 / row["device_period"])
    assert profiler._unanswered == {}


class _Failing(_Sentinel):
    def __init__(self, error):
        super().__init__()
        self._error = error

    def block_until_ready(self):
        raise self._error


@pytest.mark.parametrize("error,logged", [
    (RuntimeError("the step failed on the device"), False),
    (ValueError("a fault of the resolver's own"), True)])
def test_a_step_that_fails_resolves_to_no_facts(error, logged, profiler,
                                                caplog):
    """The device's error is the step loop's to raise: the row just lacks
    the device keys.  Any other error is logged, and ``history`` still
    comes back."""
    profiler.dispatched(_Failing(error), {})
    profiler.step_boundary()
    after = _Sentinel()
    after.finish()
    profiler.dispatched(after, {})
    profiler.step_boundary()
    with caplog.at_level("ERROR", logger=train_profiler.__name__):
        failed, fine = list(profiler.history)
    assert not set(train_profiler.DEVICE_KEYS) & set(failed)
    assert fine["done"] > 0
    assert bool(caplog.records) == logged


def test_the_hand_over_has_its_own_counter(profiler):
    """``dispatch`` stays what it was before the sentinel (the jit call);
    what the sentinel costs the worker's thread is ``hand_over``."""
    def own_step(params, opt_state, x):
        return params + x, opt_state, jnp.sum(x)

    step = jit_train_step(own_step)
    seen = {}
    hand_over = step._hand_over

    def slow_hand_over(*a):
        seen["dispatch"] = profiler._counts["dispatch"]
        time.sleep(0.05)
        hand_over(*a)
    step._hand_over = slow_hand_over
    step(jnp.ones(4), jnp.zeros(4), jnp.ones(4))
    profiler.step_boundary()
    row = profiler.history[-1]
    # counted before the hand-over began, and not grown by it
    assert row["dispatch"] == seen["dispatch"] > 0
    assert row["hand_over"] >= 0.05


def test_every_counter_gauge_reads_a_registered_counter():
    assert set(train_metrics.COUNTER_GAUGES) <= set(
        tracing.STEP_COUNTER_REGISTRY)
    # an uneven layer and one that sent the held experts nothing
    assert train_metrics.moe_load_max(
        [[[3, 1]], [[0, 0]]]) == pytest.approx(1.5)
    assert train_metrics.moe_load_max([[[0, 0]]]) is None
    assert train_metrics.moe_moved_rows([[8, 16], [64, 8]]) == 24.0


def test_the_resolver_thread_ends_with_the_session():
    p = StepProfiler(run_name="ends", rank=0)
    train_profiler.activate(p)
    s = _Sentinel()
    s.finish()
    p.dispatched(s, {})
    name = p._resolver.name
    train_profiler.activate(None)
    deadline = time.time() + 10
    while any(t.name == name for t in threading.enumerate()) \
            and time.time() < deadline:
        time.sleep(0.01)
    assert not any(t.name == name for t in threading.enumerate())
    p.step_boundary()
    assert p.history[-1]["done"] > 0  # answered before it ended


# ------------------------------------------------------------- the registry
def test_counter_names_are_under_the_registry_check():
    from ray_tpu.devtools.analysis import core
    from ray_tpu.devtools.analysis.checkers import registry_consistency

    ctx = core.AnalysisContext(root=REPO)
    core.load_registries(ctx, os.path.join(REPO, "ray_tpu"))
    assert ctx.step_counter_names == set(tracing.STEP_COUNTER_REGISTRY)
    assert "train.step_done" in ctx.span_names
    source = ("from ray_tpu.util.tracing import step_counter\n"
              "def loss(rows):\n"
              "    return 0.0, {step_counter('moe_rows'): rows,\n"
              "                 step_counter('moe_moved'): rows,\n"
              "                 step_counter('loss_main'): 0.0,\n"
              "                 step_counter('loss_mtp'): 0.0,\n"
              "                 step_counter('mhc_sinkhorn_err'): 0.0,\n"
              "                 step_counter('loss_ut'): 0.0,\n"
              "                 step_counter('ut_exit_mass'): 0.0,\n"
              "                 step_counter('moe_rowz'): rows}\n")
    module = core.SourceModule("fixture.py", "ray_tpu/models/fixture.py",
                               source)
    checker = registry_consistency.RegistryConsistencyChecker()
    findings = list(checker.check_module(module, ctx))
    assert [f.detail for f in findings] == ["step-counter:moe_rowz"]
    assert not any(f.detail.startswith("step-counter-unused:")
                   for f in checker.finalize(ctx))
    with pytest.raises(KeyError, match="STEP_COUNTER_REGISTRY"):
        tracing.step_counter("moe_rowz")


# ---------------------------------------------------------------- the cache
_CACHE_PROBE = """
import functools, json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from ray_tpu.models import llama
from ray_tpu.parallel.compile_cache import configure_compile_cache
from ray_tpu.parallel.train_state import jit_train_step
from ray_tpu.util import device_telemetry as dt
configure_compile_cache()
config = llama.LlamaConfig.tiny_moe()
optimizer = llama.make_optimizer()
device = jax.devices()[0]
for _ in range(2):
    params = jax.device_put(llama.init_params(config, jax.random.key(0)),
                            device)
    opt_state = jax.device_put(optimizer.init(params), device)
    tokens = jax.device_put(jnp.zeros((2, config.seq_len), jnp.int32), device)
    step = jit_train_step(llama.make_train_step(config, optimizer))
    jax.block_until_ready(step(params, opt_state, tokens, tokens))
print(json.dumps([r["cache"] for r in dt.compile_records("train_step")]))
"""


def test_a_second_trainstep_in_one_cache_directory_compiles_nothing(tmp_path):
    """A program with a host callback never reaches the persistent cache; a
    closed-over ref does: the second ``TrainStep`` of the same model (its
    own closure, its own ref) loads what the first one compiled."""
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], cwd=REPO, check=True,
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")))
    assert out.stdout.strip().splitlines()[-1] == '["miss", "hit"]'
