"""The program names its own work (docs/observability.md § Names inside the
step): ``jax.named_scope`` names in the compiled train step, ``TrainStep``
(anatomy, compile records, first call), the span API on the profiler's
clock, and the step profiler's counters.

* each model family's lowered step holds every registered scope that is its
  own (the three expert-layer scopes are the llama step's with experts; the
  Mamba-2 scopes and the shared expert's are the hybrid step's, the KDA
  scopes those of a hybrid step whose pattern holds ``K``, the
  gated-delta-net scopes those of one whose pattern holds ``G``, the three
  of the hyper-connections those of one with ``streams`` > 1, the exit
  gate's that of a llama step whose stack is looped);
* ``classify_op_name`` on real ``op_name`` strings of the compiled v5e steps
  (``tests/data/v5e_step_op_names.json``) and ``parse_anatomy`` on an
  excerpt of that module's text (``tests/data/v5e_step_excerpt.hlo.txt``);
* a tiny CPU step end to end through ``TrainStep.anatomy()``;
* a CPU ``jax.profiler`` trace of a tiny ``JaxTrainer.fit()`` holds the
  worker's spans on one thread line and the pump's and the controller's on
  others;
* ``StepProfiler`` rows carry the counters with the sum invariant intact;
* a forced shape change shows in ``compile_records("train_step")`` and in
  the row's ``compiles``, a steady loop records none.
"""

import collections
import functools
import glob
import json
import os

import numpy as np
import pytest

from ray_tpu.train import profiler as train_profiler
from ray_tpu.util import device_telemetry as dt
from ray_tpu.util import tracing
from tests import families

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")

with open(os.path.join(DATA, "v5e_step_op_names.json")) as f:
    OP_NAME_ROWS = json.load(f)["rows"]


@pytest.fixture(autouse=True)
def clean_telemetry():
    dt.reset()
    yield
    dt.reset()
    train_profiler.activate(None)


#: scopes only a step with models/moe.py's expert layer opens
MOE_SCOPES = {"router", "moe_dispatch", "experts"}
#: scopes only a block-diffusion step whose expert layer holds a share opens;
#: its products are ``moe_held`` where the whole layer's are ``experts``
SDAR_SCOPES = {"moe_held", "noise"}


#: scopes only a step of models/hybrid.py opens: the Mamba-2 layers and the
#: shared expert beside the routed ones
SSM_SCOPES = {"ssm", "ssm_conv", "ssm_scan"}
KDA_SCOPES = {"kda", "kda_conv", "kda_scan"}
#: scopes only a step with latent attention and a prediction module opens
MLA_SCOPES = {"latent", "mtp", "mtp_head"}
#: the scope only a stack with window-attention layers opens
WINDOW_SCOPES = {"window"}
#: scopes only a stack with gated short-convolution layers opens
CONV_SCOPES = {"shortconv", "shortconv_gate"}
#: scopes only a stack with gated-delta-net layers opens
GDN_SCOPES = {"gdn", "gdn_conv", "gdn_scan"}
#: scopes only a stack over a residual of several streams opens
MHC_SCOPES = {"mhc", "mhc_maps", "mhc_mix"}
#: the scope only a looped stack opens (``models/looped.py``)
LOOPED_SCOPES = {"exit_gate"}
HYBRID_SCOPES = SSM_SCOPES | KDA_SCOPES | MLA_SCOPES | WINDOW_SCOPES \
    | CONV_SCOPES | GDN_SCOPES | MHC_SCOPES | {"shared_expert"}


#: a hybrid step by what its pattern holds -> (the scopes only its kinds
#: open, what else of the hybrid steps' it does not open: ``hybrid-conv`` has
#: no shared expert beside its routed ones, ``hybrid-gdn`` is dense)
OWN_SCOPES = {
    "hybrid": (SSM_SCOPES, set()), "hybrid-kda": (KDA_SCOPES, set()),
    "hybrid-mla": (MLA_SCOPES, set()), "hybrid-window": (WINDOW_SCOPES, set()),
    "hybrid-conv": (CONV_SCOPES, {"shared_expert"}),
    "hybrid-gdn": (GDN_SCOPES, {"shared_expert", "moe_held"} | MOE_SCOPES),
    "hybrid-mhc": (MLA_SCOPES | MHC_SCOPES, set())}


def _scopes_of(family):
    if family in OWN_SCOPES:  # holds a share, trains next tokens
        own, without = OWN_SCOPES[family]
        return set(tracing.SCOPE_REGISTRY) - {"experts", "noise"} - without \
            - (HYBRID_SCOPES - {"shared_expert"} - own) - LOOPED_SCOPES
    if family == "llama-sdar":
        return set(tracing.SCOPE_REGISTRY) - {"experts"} - HYBRID_SCOPES \
            - LOOPED_SCOPES
    return set(tracing.SCOPE_REGISTRY) - SDAR_SCOPES - HYBRID_SCOPES - (
        set() if family == "llama-moe" else MOE_SCOPES) - (
        set() if family == "llama-ouro" else LOOPED_SCOPES)


#: the hybrid steps by what their patterns hold -> the row of
#: ``tests/families.py``: what nemotron-ep16-s8192, solar-open2-ep40-tp8,
#: joyai-ep16-s8192, laguna-ep32-s8192, lfm2-ep4-s8192, olmo-hybrid-s8192
#: and xing4-ep8-s4096 run
HYBRID_ROWS = {"hybrid": "nemotron_h", "hybrid-kda": "solar_open2",
               "hybrid-mla": "joyai_llm_flash", "hybrid-window": "laguna",
               "hybrid-conv": "lfm2_moe", "hybrid-gdn": "olmo_hybrid",
               "hybrid-mhc": "xing4_0"}


def _family(name):
    from ray_tpu.models import gpt2, llama

    if name == "llama":
        return llama, llama.LlamaConfig.tiny()
    if name == "llama-moe":  # what olmoe-s4096 runs
        return llama, llama.LlamaConfig.tiny_moe()
    if name == "llama-sdar":  # what sdar-ep8-s8192 runs
        return llama, llama.LlamaConfig.tiny_sdar()
    if name == "llama-ouro":  # what ouro-l8-s4096 runs
        return llama, llama.LlamaConfig.tiny_ouro()
    if name in HYBRID_ROWS:  # the rehearsal file of what the cell runs
        from ray_tpu.models import hybrid

        return hybrid, families.preset(HYBRID_ROWS[name])
    config = gpt2.GPTConfig.tiny()
    if name == "gpt2-attn-outside-unrolled":  # what gpt2xl-s1024 runs
        import dataclasses

        config = dataclasses.replace(config, remat_policy="attn_outside",
                                     scan_layers=False, attn_impl="auto")
    return gpt2, config


def _tiny_step(name="llama"):
    """(TrainStep, params, opt_state, tokens) of a family's tiny preset on
    one CPU device, through the entry points a train loop uses."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.parallel.train_state import (create_sharded_state,
                                              jit_train_step)

    model, config = _family(name)
    mesh = make_mesh(MeshSpec(data=1), jax.devices()[:1])
    optimizer = model.make_optimizer()
    params, opt_state = create_sharded_state(
        functools.partial(model.init_params, config),
        model.logical_axes(config), mesh, jax.random.key(0), optimizer)
    step = jit_train_step(model.make_train_step(config, optimizer),
                          mesh=mesh)
    return step, params, opt_state, jnp.zeros((2, config.seq_len),
                                              jnp.int32)


# ------------------------------------------------------- names in the step
@pytest.mark.parametrize("family", ["llama", "llama-moe", "llama-sdar",
                                    "llama-ouro",
                                    "hybrid", "hybrid-kda", "hybrid-mla",
                                    "hybrid-window", "hybrid-conv",
                                    "hybrid-gdn", "hybrid-mhc",
                                    "gpt2", "gpt2-attn-outside-unrolled"])
def test_lowered_step_holds_every_registered_scope(family):
    import jax
    import jax.numpy as jnp

    model, config = _family(family)
    optimizer = model.make_optimizer()
    params = jax.eval_shape(
        functools.partial(model.init_params, config), jax.random.key(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    tokens = jax.ShapeDtypeStruct((2, config.seq_len), jnp.int32)
    lowered = jax.jit(model.make_train_step(config, optimizer)).lower(
        params, opt_state, tokens, tokens)
    text = lowered.as_text(debug_info=True)
    for scope in tracing.SCOPE_REGISTRY:
        assert (f"/{scope}/" in text or f"({scope})" in text) \
            == (scope in _scopes_of(family)), (
            f"{family}: the lowered step and the scope {scope!r}")


def test_scopes_are_metadata_only():
    """The same jaxpr with and without the names: a scope adds no
    equation."""
    import jax
    import jax.numpy as jnp

    def named(x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x) * 2

    plain = jax.make_jaxpr(lambda x: jnp.tanh(x) * 2)(jnp.ones(3))
    assert str(jax.make_jaxpr(named)(jnp.ones(3))) == str(plain)


# ----------------------------------------------------------------- anatomy
@pytest.mark.parametrize(
    "row", OP_NAME_ROWS,
    ids=[f"{i}-{r['phase']}-{r['part']}" for i, r in enumerate(OP_NAME_ROWS)])
def test_classify_real_v5e_op_names(row):
    from ray_tpu.parallel.train_state import classify_op_name

    assert classify_op_name(row["op_name"]) == (row["phase"], row["part"])


def test_fixture_covers_every_phase_and_part():
    from ray_tpu.parallel.train_state import PHASES

    assert {r["phase"] for r in OP_NAME_ROWS} == set(PHASES) | {None}
    assert {r["part"] for r in OP_NAME_ROWS} \
        == set(tracing.SCOPE_REGISTRY) | {None}


def test_parse_anatomy_on_v5e_module_excerpt():
    from ray_tpu.parallel.train_state import parse_anatomy

    with open(os.path.join(DATA, "v5e_step_excerpt.hlo.txt")) as f:
        anatomy = parse_anatomy(f.read())
    # own op_name
    assert anatomy["multiply.151"] == ("update", "optimizer")
    assert anatomy["reduce.8"] == ("forward", "lm_head")
    assert anatomy["while.2"] == ("backward", None)
    # an instruction whose text runs over three lines (the Mosaic call)
    assert anatomy["splash_mha_fwd_residuals.16"] \
        == ("recompute", "attn_kernel")
    # a fusion named for its root (the slice update): the part comes from
    # the matmul it fuses, two fusions down
    assert anatomy["bitcast_dynamic-update-slice_fusion.7"] \
        == ("backward", "mlp")
    # a hoisted constant: a part, no phase
    assert anatomy["fusion.255"] == (None, "attn")
    # async copies carry no metadata: data movement for whoever reads it,
    # two hops up to the kernel
    assert anatomy["copy-start.86"] == anatomy["copy-done.86"] \
        == ("recompute", "attn_kernel")
    # neither: parameters, and what nothing named reads
    assert anatomy["Arg_0.1"] == (None, None)
    assert anatomy["tuple.9"] == (None, None)


@pytest.mark.parametrize("family", ["llama", "llama-moe", "llama-sdar",
                                    "llama-ouro",
                                    "hybrid", "hybrid-kda", "hybrid-mla",
                                    "hybrid-window", "hybrid-conv",
                                    "hybrid-gdn", "hybrid-mhc",
                                    "gpt2-attn-outside-unrolled"])
def test_anatomy_of_a_tiny_cpu_step_end_to_end(family):
    from ray_tpu.parallel.train_state import PHASES

    step, params, opt_state, tokens = _tiny_step(family)
    with pytest.raises(RuntimeError, match="needs a call"):
        step.anatomy()
    params, opt_state, loss = step(params, opt_state, tokens, tokens)
    assert np.isfinite(float(loss))
    anatomy = step.anatomy()
    assert step.anatomy() is anatomy  # cached
    by_phase = collections.Counter(p for p, _ in anatomy.values())
    by_part = collections.Counter(p for _, p in anatomy.values())
    for phase in PHASES:
        assert by_phase[phase] > 0, (family, phase, by_phase)
    for part in tracing.SCOPE_REGISTRY:
        assert (by_part[part] > 0) == (part in _scopes_of(family)), (
            family, part, by_part)
    # the anatomy's own compile, where jax does not find the first call's
    # executable still in memory, is labelled apart from the step's
    assert [r["trigger"] for r in dt.compile_records("train_step")] \
        == [dt.TRIGGER_FIRST]
    assert len(dt.compile_records("train_step.anatomy")) <= 1


# ------------------------------------------------- compile registry, rows
def test_shape_change_through_train_step_is_classified_and_counted():
    import jax.numpy as jnp

    step, params, opt_state, tokens = _tiny_step()
    assert dt.program("train_step") is step
    assert {r["label"] for r in dt.compile_records()} \
        >= {"init_params", "init_opt_state"}
    profiler = train_profiler.StepProfiler(run_name="unit")
    train_profiler.activate(profiler)

    def one(tok):
        nonlocal params, opt_state
        params, opt_state, _ = step(params, opt_state, tok, tok)
        return profiler.step_boundary()

    first = one(tokens)
    steady = [one(tokens) for _ in range(4)]
    assert first["compiles"] == 1 and first["compile_s"] > 0
    assert [r["compiles"] for r in steady] == [0, 0, 0, 0]
    assert all(r["dispatch"] > 0 for r in [first] + steady)
    assert [r["trigger"] for r in dt.compile_records("train_step")] \
        == [dt.TRIGGER_FIRST]

    wider = jnp.zeros((4, tokens.shape[1]), jnp.int32)
    # jnp.zeros compiled on this thread too: the row counts every compile
    # of its thread, the label says whose it was.
    assert profiler.step_boundary()["compiles"] == 1
    changed = one(wider)
    assert changed["compiles"] == 1
    assert [r["trigger"] for r in dt.compile_records("train_step")] \
        == [dt.TRIGGER_FIRST, dt.TRIGGER_SHAPE]
    calls = dt.first_calls("train_step")
    assert len(calls) == 2 and all(c["seconds"] > 0 for c in calls)
    assert calls[0]["seconds"] >= first["compile_s"]


def test_first_call_is_a_span_when_tracing_is_on():
    step, params, opt_state, tokens = _tiny_step()
    tracing.clear_spans()
    tracing.enable_tracing()
    try:
        step(params, opt_state, tokens, tokens)
        names = [s["name"] for s in tracing.exported_spans()]
    finally:
        tracing.disable_tracing()
        tracing.clear_spans()
    assert names.count("train.first_call") == 1
    assert "xla.compile" in names
    assert "train.dispatch" not in names  # profiler's clock only


def test_profiler_rows_carry_counters_and_keep_the_sum_invariant():
    profiler = train_profiler.StepProfiler(run_name="unit")
    profiler.start(now=100.0)
    train_profiler.activate(profiler)
    train_profiler.record("data_wait", 100.1, 100.3)
    train_profiler.record("h2d", 100.3, 100.35)
    train_profiler.count("dispatch", 0.002)
    train_profiler.count("h2d_bytes", 65536)
    train_profiler.count("compiles", 1)
    train_profiler.count("compile_s", 0.5)
    row = profiler.step_boundary(now=101.0)
    assert set(train_profiler.COUNTERS) <= set(row)
    assert (row["dispatch"], row["h2d_bytes"], row["compiles"],
            row["compile_s"], row["report"]) == (0.002, 65536, 1, 0.5, 0)
    waits = sum(row[b] for b in train_profiler.BUCKETS)
    assert row["compute"] + waits == pytest.approx(row["wall"], rel=1e-12)
    assert row["compute"] == pytest.approx(1.0 - 0.25)
    # counters reset with the step
    nxt = profiler.step_boundary(now=102.0)
    assert all(nxt[c] == 0 for c in train_profiler.COUNTERS)
    # off a profiled worker's thread the hooks are no-ops
    train_profiler.activate(None)
    train_profiler.count("dispatch", 1.0)


def test_device_put_batch_counts_h2d_bytes_into_the_row():
    from ray_tpu._private import jax_compat

    profiler = train_profiler.StepProfiler(run_name="unit")
    train_profiler.activate(profiler)
    jax_compat.device_put_batch({
        "tokens": np.zeros((8, 1024), np.int32),
        "targets": np.zeros((8, 1024), np.int32),
        "doc": ["a"] * 8})
    row = profiler.step_boundary()
    assert row["h2d_bytes"] == 8 * 1024 * 4 * 2  # 8 bytes a token


# ---------------------------------------------- the profiler's clock (host)
def test_span_is_a_profiler_annotation_whether_or_not_tracing_is_on():
    import jax

    assert not tracing.is_tracing_enabled()
    off = tracing.span("data.prefetch")
    assert isinstance(off._ann, jax.profiler.TraceAnnotation)
    assert isinstance(tracing.annotate("train.dispatch"),
                      jax.profiler.TraceAnnotation)
    with off as s, tracing.annotate("train.dispatch"):
        assert s is None  # the disabled span's contract
    tracing.clear_spans()
    tracing.enable_tracing()
    try:
        with tracing.span("data.prefetch") as s:
            assert s["name"] == "data.prefetch"
        with tracing.annotate("train.dispatch"):
            pass
        names = [s["name"] for s in tracing.exported_spans()]
    finally:
        tracing.disable_tracing()
        tracing.clear_spans()
    assert names == ["data.prefetch"]


def test_fit_puts_program_spans_on_the_profilers_clock(tmp_path):
    """A profile open around a tiny ``JaxTrainer.fit()``: the worker's
    thread line holds dispatch, report, prefetch and state init; the
    ingest pump and the controller's drain sit on lines of their own."""
    import jax

    import ray_tpu
    from ray_tpu import data, train
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh
    from ray_tpu.parallel.train_state import (create_sharded_state,
                                              jit_train_step)

    config = llama.LlamaConfig.tiny()
    seen = {}

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
        seen["rows"] = list(train.active_profiler().history)

    rows = np.random.default_rng(0).integers(
        0, config.vocab_size, (12, config.seq_len + 1)).astype(np.int32)
    dataset = data.from_items(
        [{"tokens": r[:-1], "targets": r[1:]} for r in rows])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    # another test on this worker may have left a runtime up (xdist hands
    # tests out by load, so the neighbours change with the suite)
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            result = train.JaxTrainer(
                loop,
                scaling_config=train.ScalingConfig(num_workers=1,
                                                   worker_mode="threads"),
                datasets={"train": dataset}).fit()
        finally:
            jax.profiler.stop_trace()
    finally:
        ray_tpu.shutdown()
    assert result.error is None, result.error

    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    lines = []  # one Counter of program span names per host thread line
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names = collections.Counter(
                    e.name for e in line.events
                    if e.name in tracing.SPAN_REGISTRY)
                if names:
                    lines.append(names)
    worker = [c for c in lines if "train.dispatch" in c]
    assert len(worker) == 1, lines
    worker = worker[0]
    assert worker["train.dispatch"] == 6 and worker["train.report"] == 6
    assert worker["data.prefetch"] == 6
    assert worker["train.init_params"] == 1
    assert worker["train.init_opt_state"] == 1
    assert worker["train.first_batch"] == 1
    # fit()'s own start is the controller's, on the line of the test's thread
    assert any("train.fit_setup" in c for c in lines if c is not worker)
    others = [c for c in lines if c is not worker]
    assert any("data.pump" in c for c in others), lines
    assert any("train.result_drain" in c for c in others), lines
    assert "data.pump" not in worker

    # The same run's rows: counters beside the buckets, invariant intact,
    # compiles on the first step only (state init's two and the step's,
    # all on the worker's thread), two int32 columns a token.
    rows = seen["rows"]
    assert len(rows) == 6
    assert rows[0]["compiles"] >= 3 and rows[0]["compile_s"] > 0
    assert [r["compiles"] for r in rows[1:]] == [0, 0, 0, 0, 0]
    assert all(r["dispatch"] > 0 and r["report"] > 0 for r in rows)
    assert sum(r["h2d_bytes"] for r in rows) == 12 * config.seq_len * 8
    for r in rows:
        waits = sum(r[b] for b in train_profiler.BUCKETS)
        assert r["compute"] + waits == pytest.approx(r["wall"], rel=1e-9)
    # The program outlives shutdown() for whoever reads it afterwards.
    assert dt.program("train_step") is not None
    assert dt.first_calls("train_step")[0]["seconds"] > 0


# ------------------------------------------------------------ static check
def test_scope_names_are_under_the_registry_check():
    from ray_tpu.devtools.analysis import core
    from ray_tpu.devtools.analysis.checkers import registry_consistency

    ctx = core.AnalysisContext(root=REPO)
    core.load_registries(ctx, os.path.join(REPO, "ray_tpu"))
    assert ctx.scope_names == set(tracing.SCOPE_REGISTRY)
    assert "device.burn" not in ctx.span_names
    for name in ("train.dispatch", "train.report", "train.first_call",
                 "data.pump", "train.result_drain", "watchdog.tick",
                 "runtime.init", "train.fit_setup", "train.first_batch"):
        assert name in ctx.span_names
    assert "train.compute" not in ctx.span_names

    source = ("import jax\n"
              "def f(x):\n"
              "    with jax.named_scope('mlp'):\n"
              "        x = x + 1\n"
              "    with jax.named_scope('mlpp'):\n"
              "        return x\n")
    module = core.SourceModule("fixture.py", "ray_tpu/models/fixture.py",
                               source)
    checker = registry_consistency.RegistryConsistencyChecker()
    findings = list(checker.check_module(module, ctx))
    assert [f.detail for f in findings] == ["scope:mlpp"]
    unused = {f.detail for f in checker.finalize(ctx)
              if f.detail.startswith("scope-unused:")}
    assert unused == {f"scope-unused:{s}" for s in tracing.SCOPE_REGISTRY
                      if s != "mlp"}


def test_ops_import_nothing_above_them():
    """``parallel/`` and ``train/`` import ``ops/``; no file under ``ops/``
    imports them back, at the top or inside a function (what a traced
    kernel says of itself goes through ``util/first_call.py``)."""
    import ast

    above = ("ray_tpu.parallel", "ray_tpu.train")
    found = []
    for path in sorted(glob.glob(os.path.join(REPO, "ray_tpu", "ops",
                                              "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = [a.name for a in node.names] \
                if isinstance(node, ast.Import) else \
                [node.module or ""] + [f"{node.module}.{a.name}"
                                       for a in node.names] \
                if isinstance(node, ast.ImportFrom) else []
            found += [(os.path.basename(path), node.lineno, name)
                      for name in names if name.startswith(above)]
    assert found == []


# ------------------------------------------------ the first-call record
_REMAT = {"remat_kept", "remat_kept_bytes", "remat_room_bytes",
          "remat_routing_bytes"}  # the last since PR 48
_STEP = {"remat_fallback", "grad_ring_products", "grad_ring_axis"}
PRESETS = sorted(name[:-len(".json")] for name in os.listdir(
    os.path.join(REPO, "benchmarks", "configs")) if name.startswith("tiny-"))


@pytest.mark.parametrize("name", PRESETS)
def test_the_first_call_record_carries_what_every_step_notes(name):
    """What a ``TrainStep`` would record of each rehearsal preset's step,
    from a trace of it under the block its call opens: what every step
    notes and, where the layers ask the remat rule (all but GPT-2's), what
    it decided.  (The values a family is about are its row's in
    ``tests/families.py``; that every key is one of ``first_call.KEYS`` is
    the call sites' test below, which reaches the arms no CPU trace takes.)"""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import grouped_matmul
    from ray_tpu.parallel.train_state import _first_call_notes

    family = families.from_file(name)
    optimizer = family.make_optimizer()
    params = jax.eval_shape(family.init_fn, jax.random.key(0))
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    with _first_call_notes() as notes:
        jax.eval_shape(family.make_train_step(optimizer), params,
                       jax.eval_shape(optimizer.init, params), ids, ids)
    assert _STEP | (set() if name == "tiny-gpt2" else _REMAT) <= set(notes)
    assert notes["remat_fallback"] is False \
        and notes["grad_ring_products"] == 0
    if "gmm_tiles" in notes:  # a product's shapes -> the tile it walks
        assert all(grouped_matmul.tile_for(*map(int, shape.split("x"))) == tile
                   for shape, tile in notes["gmm_tiles"].items())
        assert len(notes["gmm_tiles"]) >= 2  # gate / up, and down


@functools.cache
def _noted_by_the_call_sites():
    """(the literal keys of every ``first_call.note`` / ``entry`` / ``count``
    / ``noting`` call under ``ray_tpu/``, the dicts such a call spreads, as
    source text), read from the files as
    :func:`test_ops_import_nothing_above_them` reads imports: that reaches
    the kernels' arms the CPU never traces."""
    import ast

    literal, spread = set(), set()
    for path in sorted(glob.glob(os.path.join(REPO, "ray_tpu", "**", "*.py"),
                                 recursive=True)):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "first_call"
                    and node.func.attr in ("note", "entry", "count",
                                           "noting")):
                continue
            if node.func.attr in ("entry", "count"):
                assert isinstance(node.args[0], ast.Constant), (
                    f"{path}:{node.lineno}: a computed key")
                literal.add(node.args[0].value)
            literal |= {kw.arg for kw in node.keywords if kw.arg}
            spread |= {ast.unparse(kw.value) for kw in node.keywords
                       if kw.arg is None}
    return literal, spread


@functools.cache
def _spread_by_the_call_sites():
    """The keys of the dicts the call sites spread, each made here as its
    site makes it: the remat rule's decision, the splash kernel's counts
    under each of its masks, the step's start, and every kind's
    ``first_call_facts`` on a rehearsal preset that holds the kind."""
    from ray_tpu.models import hybrid, streams
    from ray_tpu.ops import attention, grad_ring, remat

    facts = set()
    for name in families.HYBRID:
        config = families.preset(name)
        for entry in hybrid._kinds(config).values():
            facts |= set(entry.module.first_call_facts(config, 2, 128))
    masks = ({}, {"block_length": 4}, {"window": 16})
    return {
        "decision.attributes()": set(remat.choose(0, 0, [], 0).attributes()),
        "counts": set().union(*(attention._splash_kernel(
            256, 4, 64, True, **mask)[1] for mask in masks)),
        "grad_ring.NO_RINGS": set(grad_ring.NO_RINGS),
        "entry.module.first_call_facts(config, rows, S)": facts,
        "streams.first_call_facts(config, len(placed))": set(
            streams.first_call_facts(families.preset("xing4_0"), 8))}


def test_every_key_a_call_site_notes_is_in_the_table():
    """``first_call.KEYS`` holds every key written under ``ray_tpu/``; a
    call site that spreads a dict this test cannot make is named."""
    from ray_tpu.util import first_call

    literal, spread = _noted_by_the_call_sites()
    made = _spread_by_the_call_sites()
    assert spread == set(made)
    assert literal - set(first_call.KEYS) == set()
    for site, keys in made.items():
        assert keys - set(first_call.KEYS) == set(), site


def test_every_key_of_the_table_has_a_writer():
    """A key that lost its writer leaves the table."""
    from ray_tpu.util import first_call

    literal, _ = _noted_by_the_call_sites()
    written = literal.union(*_spread_by_the_call_sites().values())
    assert set(first_call.KEYS) - written == set()
    assert all(says.split(":")[0].split(" ")[0].endswith(".py")
               and os.path.exists(os.path.join(
                   REPO, "ray_tpu", says.split(":")[0].split(" ")[0]))
               for says in first_call.KEYS.values())


def test_the_docs_name_every_key_of_the_table():
    """``docs/observability.md``'s table of the record points at
    ``first_call.KEYS`` and names each of its keys."""
    from ray_tpu.util import first_call

    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        text = f.read()
    assert "first_call.KEYS" in text
    assert [key for key in first_call.KEYS if f"`{key}`" not in text] == []
    assert "first_call.KEYS" in tracing.SPAN_REGISTRY["train.first_call"]
