"""allenai's Olmo-Hybrid-7B through ``models/hybrid.py``: the gated delta
rule with one decay a head as the token mixer of three layers in four (kind
``G``, ``models/gdn.py`` over ``ops/gdn.py``), attention without rotary
embedding under an RMSNorm over all of q and of k on the fourth, a dense
SwiGLU MLP after every mixer, the norm *after* each sub-layer
(``norm_after``), no experts, an untied head.

The plain reference is ``benchmarks/reference/olmo_hybrid.py``, the one copy
(float32, the recurrence one position after the other, attention a block of
queries at a time).  Everything runs on the CPU with seeded random weights
at tiny sizes, attention on the einsum path.  (``ops/gdn.py`` against the
recurrence alone is ``tests/test_gdn.py``.)
"""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import correct, cost_olmo_hybrid, spec
from benchmarks.reference import olmo_hybrid as reference
from benchmarks.reference.llama import _rmsnorm
from ray_tpu.models import attn, dense, gdn, hybrid, mamba2
from ray_tpu.ops import remat
from ray_tpu.parallel import MeshSpec, make_mesh
from ray_tpu.util import first_call, tracing

#: benchmarks/lib/correct.py's, which the bf16 program is held to on the chip
LOSS_TOL, GRAD_TOL = 1e-3, 0.75
#: the float32 program against the float32 reference: the same mathematics
#: in another order (the chunked scan's triangular inverse for 127 dependent
#: steps, splash-free attention, float32 summation order); a weight rounded
#: to bfloat16 (2^-9 a value) moves a gradient leaf by 1e-3 and more
F32_LOSS_TOL, F32_GRAD_TOL = 1e-5, 2e-4


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _tiny_family(dtype="bfloat16", **changes):
    config = dict(spec.load_json(spec.BENCH_DIR, "configs",
                                 "tiny-olmo-hybrid.json"), **changes)
    config["options"] = {"attn_impl": "xla", "dtype": jnp.dtype(dtype),
                         "logits_dtype": jnp.dtype(dtype)}
    return config, spec.load_module("models", "olmo_hybrid").build(config,
                                                                   128)


def _float32(**changes):
    return dataclasses.replace(
        hybrid.HybridConfig.tiny_olmo_hybrid(), attn_impl="xla",
        dtype=jnp.float32, logits_dtype=jnp.float32, **changes)


def _shaken(params, seed=7, decays=10.0):
    """Seeded weights on which nothing is an identity: norm weights off one,
    decays and betas that differ by position (``w_a`` and ``w_b`` times
    ``decays``), and an embedding of unit scale.  (At the initialisation's
    0.02 the first sub-layers' outputs lie
    under the norms' eps, where a norm is a constant gain and a sub-layer
    a polynomial in its input: each doubles or triples the relative error
    that reaches it, and the bf16 program's gradients read 0.13 off the
    float32 ones on every leaf.  A trained residual stream is not there.)"""
    key = jax.random.key(seed)
    for stack, names in (("gdn", ("gdn_norm", "head_norm")),
                         ("attn", ("attn_norm", "q_norm", "k_norm")),
                         ("dense", ("mlp_norm",))):
        for name in names if stack in params else ():
            key, k = jax.random.split(key)
            params[stack][name] = params[stack][name] \
                + 0.2 * jax.random.normal(k, params[stack][name].shape)
    for name in ("w_a", "w_b") if "gdn" in params else ():
        params["gdn"][name] = params["gdn"][name] * decays
    if "wte" in params:
        params["wte"] = params["wte"] * 50.0
    return params


def _rows(vocab, n=2, seed=0):
    rows = np.random.default_rng(seed).integers(0, vocab, (n, 129)).astype(
        np.int32)
    return rows[:, :-1], rows[:, 1:]


# ------------------------------------ (1) each kind under either placement
REFERENCE_CFG = {"linear_num_value_heads": 4, "linear_key_head_dim": 12,
                 "num_attention_heads": 4, "num_key_value_heads": 4,
                 "hidden_size": 128, "rms_norm_eps": 1e-6}


def _sub_layer(kind):
    """(the kind's module, its norm's name, the reference's sub-layer on an
    un-normed input)."""
    return {
        "G": (gdn, "gdn_norm",
              lambda u, w: reference.linear_attention(u, w, REFERENCE_CFG)),
        "*": (attn, "attn_norm",
              lambda u, w: reference.full_attention(u, w, REFERENCE_CFG, 64)),
        "D": (dense, "mlp_norm", reference.swiglu),
    }[kind]


@pytest.mark.parametrize("after", [True, False], ids=["after", "before"])
@pytest.mark.parametrize("kind", ["G", "*", "D"])
def test_a_layer_is_the_references_under_either_norm_placement(kind, after):
    """With ``norm_after`` a layer is ``x + norm(f(x))``, without it ``x +
    f(norm(x))``, ``f`` the reference's sub-layer and ``norm`` the kind's
    one weight: forward and every gradient, in float32."""
    module, norm, f = _sub_layer(kind)
    config = _float32(norm_after=after)
    blk = jax.tree.map(lambda a: a[0], _shaken(
        {hybrid.KINDS[kind].stack: module.init_params(
            config, jax.random.key(0), 1, 0.02)})[hybrid.KINDS[kind].stack])
    x = jax.random.normal(jax.random.key(2), (2, 64, config.d_model))
    layer = module.layer(config, module.logical_axes(config), 0)
    eps = config.rms_eps

    def ours(x, blk):
        return layer(x, blk)[0]

    def theirs(x, blk):
        if after:
            return x + _rmsnorm(f(x, blk), blk[norm], eps)
        return x + f(_rmsnorm(x, blk[norm], eps), blk)

    probe = jax.random.normal(jax.random.key(3), x.shape)
    with jax.default_matmul_precision("highest"):
        assert _rel_err(ours(x, blk), theirs(x, blk)) < 1e-5
        got, want = (jax.grad(lambda x, blk: jnp.sum(g(x, blk) * probe),
                              argnums=(0, 1))(x, blk)
                     for g in (ours, theirs))
    assert set(got[1]) == set(blk)
    for path, err in jax.tree_util.tree_flatten_with_path(
            jax.tree.map(_rel_err, got, want))[0]:
        assert err < F32_GRAD_TOL, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("kind", ["M", "K", "E", "L", "W", "C"])
def test_a_kind_that_does_not_read_norm_after_refuses_it(kind):
    """Only ``*``, ``D`` and ``G`` read the field; a pattern that holds any
    other kind under it is refused when the configuration is made, and a
    kind's module says which it is (``NORM_AFTER``)."""
    assert not hasattr(hybrid.KINDS[kind].module, "NORM_AFTER")
    with pytest.raises(ValueError, match="norm_after over the kinds"):
        hybrid.HybridConfig(pattern="GD" + kind, norm_after=True)
    hybrid.HybridConfig(pattern="GD" + kind)  # without it, any pattern
    assert [k for k, entry in hybrid.KINDS.items()
            if getattr(entry.module, "NORM_AFTER", False)] == ["*", "D", "G"]


# ------------------------------------------------------ (2) the whole model
@pytest.mark.parametrize("dtype,loss_tol,grad_tol,decays", [
    ("float32", F32_LOSS_TOL, F32_GRAD_TOL, 10.0),
    # bf16 operands, residual stream and logits under the chip run's limits,
    # at the initialisation's ``w_a``: a decay is ``exp`` of ``exp(A_log)``
    # (up to 16) times ``u w_a``, and ``u`` is the bf16 residual stream, so
    # at ten times the initialisation one rounding of ``u`` is 8 % of a
    # decay and the leaves read 0.3-0.5 off (a model's property, in any
    # bf16 program: the float32 case above holds the same weights to 2e-4)
    ("bfloat16", LOSS_TOL, GRAD_TOL, 1.0),
], ids=["float32", "bfloat16"])
def test_loss_and_gradients_match_the_plain_reference(dtype, loss_tol,
                                                      grad_tol, decays):
    config, family = _tiny_family(dtype)
    module = spec.load_module("models", "olmo_hybrid")
    assert module.pattern(config) == "GDGDGD*D" \
        == hybrid.HybridConfig.tiny_olmo_hybrid().pattern
    params = _shaken(jax.jit(family.init_fn)(jax.random.key(0)),
                     decays=decays)
    tokens, targets = _rows(family.vocab_size)
    loss, grads = jax.jit(jax.value_and_grad(family.loss_fn))(
        params, tokens, targets)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p, t, y: family.reference_loss(p, t, y, 64)))(
        params, tokens, targets)
    assert _rel_err(loss, ref_loss) < loss_tol
    errors = jax.tree.map(_rel_err, grads, ref_grads)
    assert set(errors) == {"wte", "lm_head", "gdn", "attn", "dense",
                           "final_norm"}
    assert set(errors["gdn"]) == {
        "gdn_norm", "wq", "wk", "wv", "wg", "wo", "w_a", "w_b", "A_log",
        "dt_bias", "head_norm", "conv_q", "conv_k", "conv_v"}
    assert set(errors["attn"]) == {"attn_norm", "wq", "wk", "wv", "wo",
                                   "q_norm", "k_norm"}
    for path, err in jax.tree_util.tree_flatten_with_path(errors)[0]:
        assert err < grad_tol, (jax.tree_util.keystr(path), err)


def test_bf16_master_weights_would_fail_the_float32_tolerance():
    """The float32 comparison is tight enough to see one rounding of the
    weights: the float32 program on parameters rounded to bfloat16 misses
    the reference on the parameters as drawn, by the gradients' limit."""
    _, family = _tiny_family("float32")
    params = _shaken(jax.jit(family.init_fn)(jax.random.key(0)))
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    tokens, targets = _rows(family.vocab_size)
    grads = jax.jit(jax.grad(family.loss_fn))(rounded, tokens, targets)
    ref_grads = jax.jit(jax.grad(
        lambda p, t, y: family.reference_loss(p, t, y, 64)))(
        params, tokens, targets)
    worst = max(jax.tree.leaves(jax.tree.map(_rel_err, grads, ref_grads)))
    assert worst > 5 * F32_GRAD_TOL, worst


def test_packed_documents_restart_neither_state_nor_convolution():
    """A row is one sequence to the recurrence and to the taps: a ``G``
    layer's output at a row's last position reads the row's first, across
    whatever document boundaries the ids spell, in the program and in the
    reference alike (no scan takes segment ids: ROADMAP B)."""
    config = _float32()
    blk = jax.tree.map(lambda a: a[0], gdn.init_params(
        config, jax.random.key(0), 1, 0.02))
    layer = gdn.layer(config, gdn.logical_axes(config), 0)
    x = jax.random.normal(jax.random.key(1), (1, 128, config.d_model))
    moved = x.at[0, 0].add(1.0)
    for f in (lambda x: layer(x, blk)[0],
              lambda x: reference.linear_attention(x, blk, REFERENCE_CFG)):
        assert float(jnp.abs(f(moved)[0, -1] - f(x)[0, -1]).max()) > 0


# ----------------------- (3) the accepted hybrid steps' programs are untouched
#: sha256 of the text jax lowers three hybrid presets' train steps to, as the
#: parent of PR 58 lowers them (``tiny()``, ``tiny_solar()`` and
#: ``tiny_lfm2()`` through their rehearsal files): with ``norm_after`` false
#: ``layers.attention``, ``layers.feed_forward`` and the new field, kind and
#: scopes trace nothing, so each computes what the parent computes, bit for
#: bit.  (The first two are also ``tests/test_nemotron_h.py``'s pins.)
LOWERED_STEPS = {
    "tiny-nemotron-h":
        "d911af43f10569b30e177e26e8d157abf9112a654b5409cbfe0bc5484e1f12c4",
    "tiny-solar-open2":
        "ba7f4d2464639435af96c62f55186c6f8fb59b856fd6788db50aa64331b4c1fa",
    "tiny-lfm2":
        "0d2443a51ef30f1aef6bee01cf642376ecebf14d9eaae88afeb152f43efd2a51",
}


@pytest.mark.parametrize("name", sorted(LOWERED_STEPS))
def test_without_norm_after_a_preset_lowers_to_the_parents_text(name):
    config = spec.load_json(spec.BENCH_DIR, "configs", name + ".json")
    family = spec.load_module("models", config["family"]).build(config, 128)
    optimizer = family.make_optimizer()
    params = jax.eval_shape(family.init_fn, jax.random.key(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = jax.jit(family.make_train_step(optimizer)).lower(
        params, opt_state, ids, ids).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED_STEPS[name]


# ------------------------------------------- (4) counts, sizes, the record
def test_the_preset_is_the_rehearsal_file():
    """``HybridConfig.tiny_olmo_hybrid()`` is what the family builds from
    ``tiny-olmo-hybrid.json``."""
    config = spec.load_json(spec.BENCH_DIR, "configs",
                            "tiny-olmo-hybrid.json")
    _, model = spec.load_module("models", "olmo_hybrid").model_config(
        config, 128)
    assert model == hybrid.HybridConfig.tiny_olmo_hybrid()


@pytest.mark.parametrize("name,seq_len", [("tiny-olmo-hybrid", 128),
                                          ("olmo-hybrid-7b-l4", 8192)])
def test_num_params_and_flops_are_the_adapters_and_the_cost_files(name,
                                                                  seq_len):
    """A pattern without ``E``: ``num_params`` counts the leaves that
    exist, ``flops_per_token`` is the yardstick's own count
    (``lib/cost_olmo_hybrid.py``), and the kind's ``mixer_flops`` the cost
    file's scan."""
    config = spec.load_json(spec.BENCH_DIR, "configs", name + ".json")
    module = spec.load_module("models", "olmo_hybrid")
    _, model = module.model_config(config, seq_len)
    family = module.build(config, seq_len)
    shapes = jax.eval_shape(family.init_fn, jax.random.key(0))
    assert hybrid.num_params(model) \
        == sum(a.size for a in jax.tree.leaves(shapes)) \
        == cost_olmo_hybrid.params_held(config)
    assert family.flops_per_token == hybrid.flops_per_token(model) \
        == cost_olmo_hybrid.model_flops_per_token(config, seq_len)
    assert gdn.mixer_flops(model, seq_len) \
        == cost_olmo_hybrid.scan_flops_per_position(config, seq_len)
    met = cost_olmo_hybrid.layer_matmul_params(config)
    assert (gdn.matmul_params(model, 0), attn.matmul_params(model, 0),
            dense.matmul_params(model, 0)) \
        == (met["linear"], met["full"], met["mlp"])


def test_flops_by_hand_and_the_first_call_record():
    config = dataclasses.replace(hybrid.HybridConfig.tiny_olmo_hybrid(),
                                 attn_impl="xla")
    D, S, H, dk, dv, C = 128, 128, 4, 12, 24, 32
    linear = D * H * (2 * dk + 3 * dv + 2)
    full = 4 * D * D
    scan = 2.0 * H * (C * (1.5 * dk + dv) + 3 * dk * dv)
    # the head once (the embedding is a gather); attention at half the
    # square; the scan's products a chunk of 32
    assert hybrid.flops_per_token(config) == 6.0 * (
        3 * linear + full + 4 * 3 * D * 256 + 1024 * D) \
        + 3.0 * (4.0 * D * S / 2 + 3 * scan)
    assert gdn.num_params(config) == linear + 4 * H * (2 * dk + dv) \
        + 2 * H + dv + D
    shapes = jax.eval_shape(lambda: hybrid.init_params(config,
                                                       jax.random.key(0)))
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    with first_call.noting() as notes:
        jax.eval_shape(lambda p, t: hybrid.loss_and_counters(
            p, t, t, config), shapes, ids)
    assert notes == {
        "layer_kinds": "GDGDGD*D", "loss_positions": 128,
        "attn_positions": 128, "heads_held": 4, "heads_total": 4,
        "attn_gate": False, "qk_norm": True, "dense_width": 256,
        "gdn_heads": 4, "gdn_key_dim": 12, "gdn_value_dim": 24,
        "gdn_chunk": 32, "gdn_chunks": 2 * 128 // 32,
        "gdn_scan_kernel": False, "gdn_scan_grid": None,  # heads of 12
        "remat_kept": [], "remat_kept_bytes": 0, "remat_room_bytes": None,
        "remat_routing_bytes": 0}
    assert all(f"``{key}``" in first_call.__doc__ for key in notes)


def test_the_remat_rule_is_offered_a_pattern_without_experts():
    """``_layer_sizes`` of a stack with no ``E``: the ladder's two rungs
    from ``*`` (q, k, v) and the four ``D`` layers (gate and up), no
    routing, and a bound that holds the widest layer's working set, the
    ``G`` layer's among them."""
    config = hybrid.HybridConfig.tiny_olmo_hybrid()
    shapes = jax.eval_shape(lambda: hybrid.init_params(config,
                                                       jax.random.key(0)))
    tokens, item = 2 * 128, 2
    candidates, bound = hybrid._layer_sizes(shapes, (2, 128, 128), config)
    assert dict(candidates) == {
        remat.QKV: tokens * 3 * 128 * item,
        remat.GATE_UP: 4 * 2 * tokens * 256 * item}
    working, kept, named = gdn.layer_bytes(config, tokens, 128, 1, item)
    assert (kept, named) == (0, {})
    assert working == tokens * 4 * (10 * 12 * 2 + 12 * 24 * 2
                                    + 2 * 32 * (16 + 4))
    assert working > dense.layer_bytes(config, tokens, 128, 1, item)[0]
    params = sum(4 * a.size for a in jax.tree.leaves(shapes))
    assert bound > working + params * item // 4
    # on a device that reports room the rule keeps both rungs
    decision = remat.choose(1 << 30, 0, candidates, bound)
    assert decision.kept == remat.LADDER


def test_the_delta_rule_layers_run_under_their_own_scopes():
    assert {"gdn", "gdn_conv", "gdn_scan"} <= set(tracing.SCOPE_REGISTRY)
    config = dataclasses.replace(hybrid.HybridConfig.tiny_olmo_hybrid(),
                                 attn_impl="xla")
    shapes = jax.eval_shape(lambda: hybrid.init_params(config,
                                                       jax.random.key(0)))
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = jax.jit(jax.grad(lambda p, t: hybrid.loss_fn(
        p, t, t, config))).lower(shapes, ids).as_text(debug_info=True)
    for scope in ("gdn", "gdn/gdn_conv", "gdn/gdn_scan", "attn_kernel",
                  "mlp", r"rematted_computation/gdn/gdn_scan"):
        assert re.search(rf"[(/]{scope}[)/]", text), scope
    for absent in ("router", "moe_dispatch", "shared_expert", "kda"):
        assert not re.search(rf"[(/]{absent}[)/]", text), absent


def test_hybrid_names_no_kind():
    """``hybrid.py`` learns of the kind by one line of ``KINDS``; the
    kind's convolution is ``mamba2.causal_conv`` and its scan
    ``ops/gdn.py``'s."""
    source = open(hybrid.__file__).read()
    assert "if kind ==" not in source and "gdn(" not in source
    assert hybrid.KINDS["G"].stack == "gdn"
    assert hybrid.KINDS["G"].module is gdn
    assert gdn.causal_conv is mamba2.causal_conv
    assert "def causal_conv" not in open(gdn.__file__).read()


# -------------------------------------------------- (5) the 8-bit control
def test_the_control_is_refused():
    """The reference on weights rounded to 8 bits (``tools/control.py``), in
    the program's place, comes out as not correct at the seed's parameters
    where the program's median passes with room on both sides of the limit,
    on the same rows.  (The chip's readings at the cell's own size set the
    configuration's limit, its ``check_why``.)  At this size and at the
    initialisation the program reads 0.12-0.14 where the pre-norm presets
    read 0.01: the embedding starts at 0.02 and no norm stands before a
    sub-layer, so the first sub-layers' outputs lie under the norms' eps,
    each is a polynomial of degree two or three in its input, and the
    relative error of the bf16 residual stream doubles or triples through
    each (:func:`_shaken`); the control reads 0.98-1.15."""
    control = spec.load_module("tools", "control").control
    config, family = _tiny_family()
    limit = 0.35
    mesh = make_mesh(MeshSpec(), jax.local_devices()[:1])
    rows = np.random.default_rng(0).integers(
        0, family.vocab_size, (1, 129)).astype(np.int32)
    program = correct.at_the_seed(family, mesh, 0, rows, limit)
    refused = correct.at_the_seed(control(family), mesh, 0, rows, limit)
    assert not refused["ok"], refused
    assert 2 * program["grad_norm_err_median"] < limit \
        < refused["grad_norm_err_median"] / 2, (program, refused)
