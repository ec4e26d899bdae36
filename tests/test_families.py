"""What every model family is held to, each test once and a case a row of
``tests/families.py``: the program against the plain reference in float32
and in bfloat16, the 8-bit control refused, the parameter count, the FLOPs
by hand and the first-call values the family is about, its kinds' scopes,
its expert layers' counters; then what holds the rows together: the lowered
train steps and the drawn parameters pinned (``tests/data/
lowered_steps.json``), what the remat rule is handed, and that
``models/hybrid.py`` knows its kinds through ``KINDS`` alone.

Everything runs on the CPU with seeded random weights at the rehearsal
files' sizes, attention on the einsum path; the grouped matmul has no other
path than its kernel in interpret mode.  What is about one family alone is
in that family's file.
"""

import hashlib
import importlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import correct, spec
from ray_tpu.models import hybrid, moe
from ray_tpu.ops import remat
from ray_tpu.parallel import MeshSpec, make_mesh
from ray_tpu.util import first_call, tracing
from tests import families
from tests.families import FAMILIES, HYBRID, rel_err

with open(os.path.join(os.path.dirname(__file__), "data",
                       "lowered_steps.json")) as f:
    PINS = json.load(f)
IDS = jax.ShapeDtypeStruct((2, families.SEQ_LEN), jnp.int32)


# ---------------------------------------------- (1) against the reference
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("family", FAMILIES)
def test_loss_and_gradients_match_the_plain_reference(family, dtype):
    """The program's loss and every gradient leaf against the family's plain
    reference on the same shaken parameters and rows: in float32 the same
    mathematics in another order, in bfloat16 (operands, residual stream and
    logits) under the limits ``lib/correct.py`` holds the chip run to."""
    row = FAMILIES[family]
    assert getattr(families.preset(family), "pattern", None) == row.pattern
    loss_tol, grad_tol = row.f32_tol if dtype == "float32" \
        else (families.LOSS_TOL, families.GRAD_TOL)
    got = families.compared(family, dtype)
    assert rel_err(got["loss"], got["ref_loss"]) < loss_tol
    errors = jax.tree.map(rel_err, got["grads"], got["ref_grads"])
    assert set(errors) == row.stacks
    for stack, leaves in row.leaves.items():
        assert set(errors[stack]) == leaves, stack
    for stack, leaves in row.leaves_among.items():
        assert leaves <= set(errors[stack]), stack
    for path, err in jax.tree_util.tree_flatten_with_path(errors)[0]:
        assert err < grad_tol, (jax.tree_util.keystr(path), err)


# ------------------------------------------------- (2) the 8-bit control
@pytest.mark.parametrize("family", [name for name, row in FAMILIES.items()
                                    if row.control])
def test_the_control_is_refused(family):
    """The reference on weights rounded to 8 bits (``tools/control.py``), in
    the program's place, comes out as not correct at the seed's parameters
    where the program's median passes, on the same rows, with room on both
    sides of the tiny preset's limit.  (The chip's readings at the cell's
    own size set the configuration's own limit, its ``check_why``.)"""
    how = FAMILIES[family].control
    control = spec.load_module("tools", "control").control
    _, built = families.built(family)
    mesh = make_mesh(MeshSpec(), jax.local_devices()[:1])
    for seed in how.seeds:
        rows = np.random.default_rng(seed).integers(
            0, built.vocab_size, (1, families.SEQ_LEN + 1)).astype(np.int32)
        program = correct.at_the_seed(built, mesh, seed, rows, how.limit)
        refused = correct.at_the_seed(control(built), mesh, seed, rows,
                                      how.limit)
        assert not refused["ok"], refused
        assert 2 * program["grad_norm_err_median"] < how.limit \
            < refused["grad_norm_err_median"] / 2, (program, refused)
        if how.program_ok:
            assert program["ok"], program
        if how.leaves_outside:
            assert all(err < program["leaf_tol"] for leaf, err
                       in program["grad_norm_err_by_leaf"].items()
                       if how.leaves_outside not in leaf), program
            assert refused["grad_norm_err_max"] > refused["leaf_tol"]


# ------------------------- (3) counts, and what the step says of itself
@pytest.mark.parametrize("family", FAMILIES)
def test_num_params_flops_and_the_first_call_record(family):
    """``num_params`` is the leaves that exist here, ``flops_per_token`` the
    row's figure written out by hand, and a trace of the loss notes the
    values the family is about (the keys others make are their tests')."""
    row = FAMILIES[family]
    config = families.preset(family, attn_impl="xla")
    # ``models/hybrid.py`` or ``models/llama.py``: the configuration's
    module = importlib.import_module(type(config).__module__)
    shapes = jax.eval_shape(lambda: module.init_params(config,
                                                       jax.random.key(0)))
    assert module.num_params(config) == sum(
        a.size for a in jax.tree.leaves(shapes))
    if row.flops is not None:
        assert module.flops_per_token(config) == row.flops
    with first_call.noting() as notes:
        jax.eval_shape(lambda p, t: module.loss_and_counters(
            p, t, t, config), shapes, IDS)
    assert row.first_call and {key: notes.get(key)
                               for key in row.first_call} == row.first_call
    if row.pattern:  # a pattern notes no kind's facts but its own
        def facts(kind, config):
            return set(hybrid.KINDS[kind].module.first_call_facts(
                config, 2, families.SEQ_LEN))

        own = set().union(*(facts(kind, config) for kind in row.pattern))
        others = set().union(*(
            facts(kind, families.preset(families.HOLDER[kind]))
            for kind in hybrid.KINDS if kind not in row.pattern)) - own
        assert not others & set(notes), others & set(notes)
        assert ("mtp_depth" in notes) == bool(config.mtp_depth)


# ------------------------------------------------ (4) the kinds' own scopes
@pytest.mark.parametrize("family", [name for name, row in FAMILIES.items()
                                    if row.scopes])
def test_the_familys_kinds_run_under_their_own_scopes(family):
    """The lowered gradient holds the scopes of the family's kinds, nested
    as the row says (a window layer's kernel under ``window``, a gate under
    its convolution, a scan recomputed in the backward), and none of the
    kinds it does not hold."""
    row = FAMILIES[family]
    assert set(row.registered) <= set(tracing.SCOPE_REGISTRY)
    config = families.preset(family, attn_impl="xla")
    # ``models/hybrid.py`` or ``models/llama.py``: the configuration's
    module = importlib.import_module(type(config).__module__)
    shapes = jax.eval_shape(lambda: module.init_params(config,
                                                       jax.random.key(0)))
    text = jax.jit(jax.grad(lambda p, t: module.loss_fn(
        p, t, t, config))).lower(shapes, IDS).as_text(debug_info=True)
    for scope in row.scopes:
        assert re.search(rf"[(/]{scope}[)/]", text), scope
    for absent in row.no_scopes:
        assert not re.search(rf"[(/]{absent}[)/]", text), absent


# ---------------------------------------- (5) the expert layers' counters
@pytest.mark.parametrize("family", [name for name in HYBRID
                                    if "E" in FAMILIES[name].pattern])
def test_counters_leave_the_step_stacked_by_expert_layer(family):
    """``moe_rows`` (expert layers, batch shards, held experts) and
    ``moe_moved`` (layers, shards), a prediction module's layer last: no
    layer counts more than the step's pairs, and what moved is whole
    windows."""
    config = families.preset(family, attn_impl="xla")
    params = families.drawn(family)
    ids = np.random.default_rng(1).integers(
        0, config.vocab_size, (2, families.SEQ_LEN)).astype(np.int32)
    _, counts = jax.jit(lambda p: hybrid.loss_and_counters(
        p, ids, ids, config))(params)
    layers, held = config.rows("E"), len(config.held)
    assert (layers, held) == (FAMILIES[family].pattern.count("E")
                              + config.mtp_depth, 4)
    # a prediction module's two losses leave beside them, and only its; a
    # residual of several streams' counter, and only its
    assert set(counts) == {"moe_rows", "moe_moved"} | (
        {"loss_main", "loss_mtp"} if config.mtp_depth else set()) | (
        {"mhc_sinkhorn_err"} if config.streams > 1 else set())
    assert counts["moe_rows"].shape == (layers, 1, held)
    assert counts["moe_moved"].shape == (layers, 1)
    pairs = ids.size * config.experts_per_token
    assert np.all(np.asarray(counts["moe_rows"]).sum(-1) <= pairs)
    assert np.all(np.asarray(counts["moe_moved"])
                  % moe.window_rows(pairs) == 0)


# ------------------------------------- (6) the programs and draws that stand
def test_every_rehearsal_file_is_pinned_once():
    files = {name[:-len(".json")] for name in os.listdir(
        os.path.join(spec.BENCH_DIR, "configs")) if name.startswith("tiny-")}
    assert set(PINS["steps"]) == files
    assert {row.preset for row in FAMILIES.values()} <= files
    assert set(PINS["init_params"]) == {FAMILIES[name].preset
                                        for name in HYBRID}


@pytest.mark.parametrize("preset", PINS["steps"])
def test_a_preset_lowers_to_the_pinned_text(preset):
    """``tests/data/lowered_steps.json``: the text a PR that did not mean to
    change a step leaves as it was (its ``recorded`` says which PRs meant
    to)."""
    built = families.from_file(preset)
    optimizer = built.make_optimizer()
    params = jax.eval_shape(built.init_fn, jax.random.key(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    text = jax.jit(built.make_train_step(optimizer)).lower(
        params, opt_state, IDS, IDS).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PINS["steps"][preset]


@pytest.mark.parametrize("preset", PINS["init_params"])
def test_the_parameters_are_the_parents_bit_for_bit(preset):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            jax.jit(families.from_file(preset).init_fn)(
                jax.random.key(0)))[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PINS["init_params"][preset]


def _cell_config(name):
    """(the configuration a cell of the benchmark trains, its batch)."""
    cell = spec.load_cell(spec.load_benchmark(), name)
    config, traffic = cell["config_file"], cell["traffic_file"]
    model = spec.load_module("models", config["family"]).model_config(
        config, traffic["seq_len"])[1]
    return model, (traffic["seqs_per_chip"] * cell["chips"],
                   traffic["seq_len"])


#: ``hybrid._layer_sizes``, pinned: (what the layers name, a chip's bytes
#: of each name over the layers that name it; the bound on the step's
#: temporaries): what ``ops/remat.py`` decides from.  Re-pinned by PR 63,
#: which made the bound the fullest moment of the unrolled step under the
#: path each kind's ops take (on this CPU XLA's forms; the two cells' rows
#: likewise: the scans' kernels by their shapes, the convolution's pass
#: only on the chip's backend) and gave the
#: kinds their own rungs: ``in_proj``'s output of the four Mamba-2 layers,
#: the three projections of the three KDA layers.  Behind them since PR 48
#: what the expert layers' routing takes (``moe.routing_bytes`` a layer),
#: which the rule keeps whatever it decides: four layers each, of 256 tokens
#: with 16 experts and 2 a token, of 16,384 with 128 and 6, of 8,192 with
#: 320 and 8.  The two tiny rows are the rehearsal files at the widths the
#: pins were recorded on (``HybridConfig``'s defaults, which the program's
#: own presets of that day took).
LAYER_SIZES = {
    "tiny-nemotron-h": (lambda: (families.preset(
        "nemotron_h", vocab_size=1024, d_model=128, d_ff=64,
        shared_width=128), (2, 128)),
        {"ssm_in_proj": 671744, "attn_qkv": 131072, "mlp_gate_up": 262144,
         "moe_routing": 16 * (256 * (16 + 10) + 16)}, 383104544),
    "tiny-solar-open2": (lambda: (families.preset(
        "solar_open2", vocab_size=1024, d_model=128), (2, 128)),
        {"attn_qkv": 65536, "mlp_gate_up": 196608, "conv_in_proj": 147456,
         "moe_routing": 16 * (256 * (16 + 10) + 16)}, 339937936),
    "nemotron-ep16-s8192": (
        lambda: _cell_config("nemotron-ep16-s8192"),
        {"ssm_in_proj": 4 * 16384 * 10304 * 2, "attn_qkv": 150994944,
         "mlp_gate_up": 486539264,
         "moe_routing": 16 * (16384 * (128 + 30) + 128)}, 4488389376),
    "solar-open2-ep40-tp8": (
        lambda: _cell_config("solar-open2-ep40-tp8"),
        {"attn_qkv": 20971520, "mlp_gate_up": 167772160,
         "conv_in_proj": 3 * 8192 * 3 * 8 * 128 * 2,
         "moe_routing": 16 * (8192 * (320 + 40) + 320)}, 4058932832),
}


@pytest.mark.parametrize("name", sorted(LAYER_SIZES))
def test_the_remat_rule_is_given_the_parents_sizes(name):
    build, named, temporaries = LAYER_SIZES[name]
    config, (rows, seq_len) = build()
    shapes = jax.eval_shape(lambda: hybrid.init_params(config,
                                                       jax.random.key(0)))
    rungs, bound = hybrid._layer_sizes(
        shapes, (rows, seq_len, config.d_model), config)
    assert (families.named(rungs), bound) == (named, temporaries)
    assert rungs[-1].name == "moe_routing" and all(
        rung.layers == config.sublayers.count(rung.group) for rung in rungs)


# ----------------------------------- (7) a kind is one entry of ``KINDS``
def test_hybrid_names_no_kind():
    """``hybrid.py`` learns of a kind by one line of ``KINDS``: it branches
    on no letter and calls no kind's mixer by name."""
    source = open(hybrid.__file__).read()
    assert "if kind ==" not in source
    for call in ("window_attention", "gated_conv", "gdn(", "ssd(", "kda("):
        assert call not in source, call
    assert sorted(hybrid.KINDS) == sorted("MKE*LDWCG")
    stacks = [entry.stack for entry in hybrid.KINDS.values()]
    draws = [entry.draw for entry in hybrid.KINDS.values()]
    assert len(set(stacks)) == len(set(draws)) == len(hybrid.KINDS)


_LOADS = """
import sys, jax, ray_tpu, ray_tpu.models.llama
assert 'ray_tpu.models.hybrid' not in sys.modules
from ray_tpu.models import hybrid
scans = {kind: 'ray_tpu.ops.' + name
         for kind, name in (('M', 'ssd'), ('K', 'kda'), ('G', 'gdn'))}
late = {kind: {entry.source} | {scans.get(kind, entry.source)}
        for kind, entry in hybrid.KINDS.items()}
ids = jax.ShapeDtypeStruct((2, 128), 'int32')
for kind in hybrid.KINDS:
    for later in list(hybrid.KINDS)[list(hybrid.KINDS).index(kind):]:
        assert not late[later] & set(sys.modules), (kind, later)
    c = hybrid.HybridConfig(pattern=kind)
    hybrid.num_params(c); hybrid.flops_per_token(c)
    p = jax.eval_shape(lambda: hybrid.init_params(c, jax.random.key(0)))
    jax.eval_shape(lambda p, t: hybrid.loss_fn(p, t, t, c), p, ids)
    assert late[kind] <= set(sys.modules), kind
"""


def test_a_kinds_modules_load_with_the_first_pattern_that_holds_it():
    """``ray_tpu`` and ``ray_tpu.models.llama`` load no ``models/hybrid.py``,
    and that loads no kind: a kind's module, and under ``ops/`` the scan of
    the three recurrent ones, load when a pattern with its letter is first
    counted, initialised and traced, in the order of ``KINDS``, and none of
    the kinds after it does."""
    done = subprocess.run([sys.executable, "-c", _LOADS], capture_output=True,
                          text=True, cwd=spec.ROOT, env={
                              "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert done.returncode == 0, done.stderr[-2000:]


#: what the docstring of ``models/hybrid.py`` says a kind's module answers
INTERFACE = ("init_params", "logical_axes", "matmul_params", "num_params",
             "mixer_flops", "layer_bytes", "first_call_facts", "layer")
@pytest.mark.parametrize("kind", hybrid.KINDS)
def test_a_kinds_module_answers_the_whole_interface(kind):
    """Every function the docstring of ``models/hybrid.py`` lists, on a
    preset that holds the kind: a stack of ``n`` layers whose axes name
    every leaf, counts that are the leaves', facts that are keys of the
    first-call record; and ``NORM_AFTER`` stated by ``*``, ``D`` and ``G``
    alone."""
    entry = hybrid.KINDS[kind]
    module = entry.module
    assert all(callable(getattr(module, name, None)) for name in INTERFACE)
    assert kind in hybrid.__doc__ and entry.source.rsplit(".", 1)[1] \
        in hybrid.__doc__
    assert getattr(module, "NORM_AFTER", False) is (kind in "*DG")
    config = families.preset(families.HOLDER[kind])
    stack = jax.eval_shape(lambda: module.init_params(
        config, jax.random.key(0), 3, 0.01))
    assert all(a.shape[0] == 3 for a in jax.tree.leaves(stack))
    axes = module.logical_axes(config)
    assert jax.tree.map(len, axes, is_leaf=lambda a: isinstance(a, tuple)) \
        == jax.tree.map(lambda a: len(a.shape), stack)
    assert all(a[0] == "layers" for a in jax.tree.leaves(
        axes, is_leaf=lambda a: isinstance(a, tuple)))
    assert 3 * module.num_params(config) == sum(
        a.size for a in jax.tree.leaves(stack))
    assert module.matmul_params(config, 1) <= module.num_params(config)
    assert module.mixer_flops(config, families.SEQ_LEN) >= 0
    working, kept, named = module.layer_bytes(config, 256, 128, 1, 2)
    assert working > 0 and kept >= 0 and all(
        size > 0 and (spares > 0 or name == remat.ROUTING)
        for name, (size, spares) in named.items())
    facts = module.first_call_facts(config, 2, families.SEQ_LEN)
    assert facts and set(facts) <= set(first_call.KEYS)
