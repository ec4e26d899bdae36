"""NVIDIA Nemotron-3-Nano through ``models/hybrid.py``: a stack of Mamba-2
(``models/mamba2.py`` over ``ops/ssd.py``), attention and expert layers
(``models/layers.py``, ``models/moe.py`` with sigmoid scores, a selection
bias, two-matrix relu^2 experts and a shared expert).

The plain reference is ``benchmarks/reference/nemotron_h.py``, the one copy
(float32, the recurrence position by position, every held expert applied to
every position).  Everything runs on the CPU with seeded random weights at
tiny sizes, attention on the einsum path; the grouped matmul has no other
path than its kernel in interpret mode.
"""

import dataclasses
import hashlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.lib import correct, spec
from benchmarks.reference import nemotron_h as reference
from ray_tpu.models import experts, hybrid, mamba2, moe
from ray_tpu.ops.ssd import ssd
from ray_tpu.parallel import MeshSpec, make_mesh
from ray_tpu.parallel.train_state import (create_sharded_state,
                                          jit_train_step)
from ray_tpu.util import device_telemetry, first_call

#: benchmarks/lib/correct.py's, which the bf16 program is held to on the chip
LOSS_TOL, GRAD_TOL = 1e-3, 0.75


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _tiny_family(dtype="bfloat16", **changes):
    config = dict(spec.load_json(spec.BENCH_DIR, "configs",
                                 "tiny-nemotron-h.json"), **changes)
    config["options"] = {"attn_impl": "xla", "dtype": jnp.dtype(dtype),
                         "logits_dtype": jnp.dtype(dtype)}
    return config, spec.load_module("models", "nemotron_h").build(config, 128)


# ------------------------------------------------------- (1) the chunked scan
def _scan_inputs(chunks, chunk=8, b=2, H=4, P=8, G=2, N=16):
    S = chunks * chunk
    k = jax.random.split(jax.random.key(chunks), 6)
    return dict(
        x=jax.random.normal(k[0], (b, S, H, P)),
        # delta A adds up to several hundred a chunk: exp of the cumulative
        # sum itself underflows, its reciprocal overflows
        dt=jax.random.normal(k[1], (b, S, H)) + 3.0,
        A_log=jax.random.uniform(k[2], (H,), minval=0.0, maxval=3.5),
        B=jax.random.normal(k[3], (b, S, G, N)),
        C=jax.random.normal(k[4], (b, S, G, N)),
        D=jax.random.normal(k[5], (H,)))


def _chunked(a, chunk):
    return ssd(a["x"], jax.nn.softplus(a["dt"]), -jnp.exp(a["A_log"]),
               a["B"], a["C"], a["D"], chunk)


def _position_by_position(a):
    J = a["x"].shape[2] // a["B"].shape[2]
    return reference.recurrence(
        a["x"], jax.nn.softplus(a["dt"]), -jnp.exp(a["A_log"]),
        jnp.repeat(a["B"], J, axis=2), jnp.repeat(a["C"], J, axis=2), a["D"])


@pytest.mark.parametrize("chunks", [2, 3, 5])
def test_chunked_scan_is_the_recurrence(chunks):
    """Forward and every gradient (x, B, C, dt, A_log, D) against a
    position-by-position ``lax.scan`` in float32, two rows a batch, with
    ``delta A`` so large that a product of ratios would overflow."""
    a = _scan_inputs(chunks)
    decay = jax.nn.softplus(a["dt"]) * -jnp.exp(a["A_log"])
    assert float(jnp.min(jnp.sum(decay.reshape(2, chunks, 8, -1),
                                 axis=2))) < -200  # exp(200) is no float32
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda a: _chunked(a, 8), a)
        want, vjp_ref = jax.vjp(_position_by_position, a)
        assert _rel_err(got, want) < 1e-5
        dy = jax.random.normal(jax.random.key(9), want.shape)
        (grads,), (grads_ref,) = vjp(dy), vjp_ref(dy)
    for name in a:
        assert np.all(np.isfinite(grads[name])), name
        # float32 sums in another order; A_log's is one sum over everything
        assert _rel_err(grads[name], grads_ref[name]) < 1e-3, name


def test_scan_products_are_in_the_inputs_dtype():
    """bf16 in: bf16 products with float32 accumulation, within bf16's
    rounding of the float32 recurrence."""
    a = _scan_inputs(3)
    low = dict(a, **{k: a[k].astype(jnp.bfloat16) for k in ("x", "B", "C")})
    got = _chunked(low, 8)
    assert got.dtype == jnp.bfloat16
    assert _rel_err(got, _position_by_position(a)) < 0.05


# ------------------------------------------------------------- (2) the mixer
def _mixer_parts(dtype=jnp.float32):
    config = dataclasses.replace(hybrid.HybridConfig.tiny(), dtype=dtype)
    blk = jax.tree.map(lambda a: a[0], mamba2.init_params(
        config, jax.random.key(0), 1, 0.02))
    # every vector away from its start, so that a lost one shows
    noise = iter(jax.random.split(jax.random.key(1), len(blk)))
    blk = {name: a + 0.1 * jax.random.normal(next(noise), a.shape)
           if a.ndim == 1 else a for name, a in blk.items()}
    x = jax.random.normal(jax.random.key(2), (2, 64, config.d_model), dtype)
    cfg = {"mamba_num_heads": config.ssm_heads,
           "mamba_head_dim": config.ssm_head_dim,
           "n_groups": config.ssm_groups, "ssm_state_size": config.ssm_state,
           "layer_norm_epsilon": config.gate_norm_eps}
    return config, blk, x, cfg


def test_mixer_matches_the_reference():
    config, blk, x, cfg = _mixer_parts()
    axes = mamba2.logical_axes(config)
    with jax.default_matmul_precision("highest"):
        got = mamba2.mixer(x, blk, config, axes) - x
        u = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + config.rms_eps) * blk["ssm_norm"]
        want = reference.mamba(u, blk, cfg)
    assert _rel_err(got, want) < 1e-5


def test_the_convolution_is_causal():
    """Changing position t changes no output before t, and the row's first
    outputs see zeros before it."""
    config, blk, x, _ = _mixer_parts()
    axes = mamba2.logical_axes(config)
    t = 37
    moved = x.at[:, t].add(1.0)
    a, b = (mamba2.mixer(v, blk, config, axes) for v in (x, moved))
    assert np.array_equal(np.asarray(a[:, :t]), np.asarray(b[:, :t]))
    assert not np.allclose(np.asarray(a[:, t]), np.asarray(b[:, t]))
    w, bias = blk["conv_w"], blk["conv_b"]
    signal = jax.random.normal(jax.random.key(3), (1, 8, w.shape[1]))
    out = mamba2.causal_conv(signal, w, bias)
    assert np.allclose(out[0, 0], bias + w[-1] * signal[0, 0], atol=1e-6)
    assert np.allclose(out, reference.conv(signal, w, bias), atol=1e-6)


def test_the_gated_norm_is_over_each_group():
    y = jax.random.normal(jax.random.key(0), (3, 32))
    z = jax.random.normal(jax.random.key(1), (3, 32))
    scale = jnp.linspace(0.5, 1.5, 32)
    got = mamba2.gated_norm(y, z, scale, 4, 1e-5)
    gated = np.asarray(y * jax.nn.silu(z)).reshape(3, 4, 8)
    want = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    assert np.allclose(got, want.reshape(3, 32) * np.asarray(scale),
                       atol=1e-5)
    # a group's scale does not reach its neighbour
    louder = mamba2.gated_norm(y.at[:, :8].multiply(100.0), z, scale, 4, 1e-5)
    assert np.allclose(louder[:, 8:], got[:, 8:], atol=1e-6)


# ------------------------------------------------------------ (3) the router
def _router(T=256, D=32, E=16):
    h = jax.random.normal(jax.random.key(0), (T, D))
    w = jax.random.normal(jax.random.key(1), (D, E)) * 0.3
    bias = jnp.zeros(E).at[3].set(1.0)  # expert 3 is under-loaded
    return h, w, bias


def test_the_bias_picks_the_experts_and_does_not_weigh_them():
    h, w, bias = _router()
    k = 4
    weights, experts, losses = moe.route(h, w, k, True, scoring="sigmoid",
                                         scale=2.5)
    biased, chosen, _ = moe.route(h, w, k, True, scoring="sigmoid",
                                  bias=bias, scale=2.5)
    # who is chosen changes: with +1 on its score everyone takes expert 3
    assert np.mean(np.any(np.asarray(experts) == 3, axis=-1)) < 0.6
    assert np.all(np.any(np.asarray(chosen) == 3, axis=-1))
    # the weights are the scores at the chosen experts without the bias,
    # renormalised, times the scale: they sum to 2.5
    assert np.allclose(np.sum(biased, -1), 2.5, atol=1e-5)
    assert np.allclose(np.sum(weights, -1), 2.5, atol=1e-5)
    scores = np.asarray(jax.nn.sigmoid(jnp.einsum(
        "td,de->te", h, w, precision="highest")))
    picked = np.take_along_axis(scores, np.asarray(chosen), axis=-1)
    assert np.allclose(biased, 2.5 * picked / picked.sum(-1, keepdims=True),
                       atol=1e-5)
    assert float(losses[0]) == float(losses[1]) == 0.0  # no auxiliary loss


def test_the_bias_has_no_gradient():
    h, w, bias = _router()
    grad = jax.grad(lambda b: jnp.sum(jnp.sin(moe.route(
        h, w, 4, True, scoring="sigmoid", bias=b, scale=2.5)[0])))(bias)
    assert not np.any(np.asarray(grad))


def test_the_bias_is_no_leaf_and_survives_an_optimizer_step():
    """The selection bias is a function of the configuration: the parameter
    tree holds no leaf for it, so the optimizer has nothing to update, and
    after a step that moved every parameter the layers draw the same bias."""
    config = dataclasses.replace(hybrid.HybridConfig.tiny(), attn_impl="xla",
                                 dtype=jnp.float32,
                                 logits_dtype=jnp.float32)
    before = [experts.router_bias(config, i) for i in range(4)]
    assert all(b.shape == (config.n_experts,) and np.any(b) for b in before)
    assert not np.allclose(before[0], before[1])  # a draw a layer
    params = hybrid.init_params(config, jax.random.key(0))
    sizes = {a.shape for a in jax.tree.leaves(params["experts"])}
    assert (config.count("E"), config.n_experts) not in sizes
    optimizer = optax.adamw(1e-2)
    step = jax.jit(hybrid.make_train_step(config, optimizer))
    ids = np.random.default_rng(0).integers(0, 1024, (2, 129)).astype(
        np.int32)
    moved, _, loss = step(params, optimizer.init(params), ids[:, :-1],
                          ids[:, 1:])
    assert np.isfinite(float(loss))
    assert all(not np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(moved)))
    after = [experts.router_bias(config, i) for i in range(4)]
    assert all(np.array_equal(a, b) for a, b in zip(before, after))


def test_the_bias_changes_some_of_the_choices():
    """At the tiny preset's spread the bias changes some tokens' experts and
    leaves others': the mechanism is no no-op and does not take over."""
    config, family = _tiny_family("float32")
    h = jax.random.normal(jax.random.key(0), (512, config["hidden_size"]))
    w = jax.random.normal(jax.random.key(1), (config["hidden_size"], 16)) \
        * 0.02 * np.sqrt(2688 / 64)  # the published width's spread of logits
    k = config["num_experts_per_tok"]
    bias = reference.selection_bias(config, 0)
    plain = np.sort(moe.route(h, w, k, True, scoring="sigmoid")[1], -1)
    biased = np.sort(moe.route(h, w, k, True, scoring="sigmoid",
                               bias=jnp.asarray(bias))[1], -1)
    changed = np.mean(np.any(plain != biased, axis=-1))
    assert 0.02 < changed < 0.9, changed


# ----------------------------------------------------- (4) the shares add up
@pytest.mark.parametrize("shares", [2, 4, 16])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """16 experts cut in ``shares``: the routed parts that all the shares
    give, plus the shared expert counted once, are the uncut reference's
    layer."""
    D, E, F, Fs, k = 32, 16, 24, 40, 3
    ks = jax.random.split(jax.random.key(shares), 6)
    whole = {"router": jax.random.normal(ks[0], (D, E)) * 0.5,
             "w_up": jax.random.normal(ks[1], (E, D, F)) * 0.2,
             "w_down": jax.random.normal(ks[2], (E, F, D)) * 0.2,
             "shared_up": jax.random.normal(ks[3], (D, Fs)) * 0.2,
             "shared_down": jax.random.normal(ks[4], (Fs, D)) * 0.2}
    h = jax.random.normal(ks[5], (2, 64, D))
    cfg = {"experts_held": [0, E], "num_experts_per_tok": k,
           "norm_topk_prob": True, "routed_scaling_factor": 2.5,
           "n_routed_experts_published": E, "router_bias_seed": 7,
           "router_bias_std": 0.1}
    bias = jnp.asarray(reference.selection_bias(cfg, 0))
    layer = jax.jit(lambda blk, first: moe.moe_mlp(
        h, blk, experts_per_token=k, norm_topk_prob=True, dtype=jnp.float32,
        first_held=first, scoring="sigmoid", bias=bias, scale=2.5,
        activation=moe.relu2)[0], static_argnums=1)
    with jax.default_matmul_precision("highest"):
        want = reference.experts(h.reshape(-1, D), whole, cfg, 0)
        held = E // shares
        total = jnp.zeros_like(h)
        for share in range(shares):
            first = share * held
            blk = {"router": whole["router"],
                   "w_up": whole["w_up"][first:first + held],
                   "w_down": whole["w_down"][first:first + held]}
            if share == 0:  # what every chip computes alike, counted once
                blk.update(shared_up=whole["shared_up"],
                           shared_down=whole["shared_down"])
            total = total + layer(blk, first)
    assert _rel_err(total.reshape(-1, D), want) < 1e-5


# ------------------------------------------------------ (5) the whole model
@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    # the same mathematics in another order: float32 summation order only
    ("float32", 1e-5, 2e-4),
    # bf16 operands, residual stream and logits under the chip run's limits
    ("bfloat16", LOSS_TOL, GRAD_TOL),
], ids=["float32", "bfloat16"])
def test_loss_and_gradients_match_the_plain_reference(dtype, loss_tol,
                                                      grad_tol):
    config, family = _tiny_family(dtype)
    assert config["hybrid_override_pattern"] == "MEMEM*EME"
    params = jax.jit(family.init_fn)(jax.random.key(0))
    # a router that prefers some experts and a scan whose decays matter
    params["experts"]["router"] = params["experts"]["router"] * 20.0
    params["ssm"]["in_proj"] = params["ssm"]["in_proj"] * 5.0
    rows = np.random.default_rng(0).integers(
        0, family.vocab_size, (2, 129)).astype(np.int32)
    tokens, targets = rows[:, :-1], rows[:, 1:]
    loss, grads = jax.jit(jax.value_and_grad(family.loss_fn))(
        params, tokens, targets)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p, t, y: family.reference_loss(p, t, y, 64)))(
        params, tokens, targets)
    assert _rel_err(loss, ref_loss) < loss_tol
    errors = jax.tree.map(_rel_err, grads, ref_grads)
    assert set(errors) == {"wte", "ssm", "attn", "experts", "final_norm",
                           "lm_head"}
    for path, err in jax.tree_util.tree_flatten_with_path(errors)[0]:
        assert err < grad_tol, (jax.tree_util.keystr(path), err)


def test_counters_leave_the_step_stacked_by_expert_layer():
    config = dataclasses.replace(hybrid.HybridConfig.tiny(), attn_impl="xla")
    params = hybrid.init_params(config, jax.random.key(0))
    ids = np.random.default_rng(1).integers(0, 1024, (2, 128)).astype(
        np.int32)
    _, counts = jax.jit(lambda p: hybrid.loss_and_counters(
        p, ids, ids, config))(params)
    assert counts["moe_rows"].shape == (4, 1, 4)   # E layers, shards, held
    assert counts["moe_moved"].shape == (4, 1)
    pairs = 2 * 128 * config.experts_per_token
    assert np.all(np.asarray(counts["moe_rows"]).sum(-1) <= pairs)
    assert np.all(np.asarray(counts["moe_moved"])
                  % moe.window_rows(pairs) == 0)


def test_num_params_and_the_first_call_record():
    config = dataclasses.replace(hybrid.HybridConfig.tiny(), attn_impl="xla")
    shapes = jax.eval_shape(lambda: hybrid.init_params(config,
                                                       jax.random.key(0)))
    assert hybrid.num_params(config) == sum(
        a.size for a in jax.tree.leaves(shapes))
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    with first_call.noting() as notes:
        jax.eval_shape(lambda p, t: hybrid.loss_and_counters(
            p, t, t, config), shapes, ids)
    assert notes == {
        "layer_kinds": "MEMEM*EME", "ssm_heads": 8, "ssm_state": 16,
        "ssm_chunk": 32, "ssm_chunks": 8, "ssm_scan_kernel": False,
        "ssm_scan_grid": None, "experts_held": 4,
        "experts_total": 16, "router_scoring": "sigmoid",
        "attn_positions": 128, "loss_positions": 128,
        # the attention kind's own since PR 46, whatever else the pattern holds
        "heads_held": 4, "heads_total": 4, "attn_gate": False,
        "remat_kept": [], "remat_kept_bytes": 0, "remat_room_bytes": None,
        # four expert layers' routing, kept under every policy (PR 48)
        "remat_routing_bytes": 4 * moe.routing_bytes(256, 16, 2),
        # a window's products, up and down, and the tiles they walk (PR 50)
        "gmm_tiles": {"64x128x64": (64, 128, 64), "64x64x128": (64, 64, 128)},
        # off the chip a window returns by the gather (PR 57)
        "moe_return": {"64x256x2x128": ("gather", None)}}


# -------------------------------------------------- (6) the 8-bit control
def test_the_control_is_refused():
    """The reference on weights rounded to 8 bits (``tools/control.py``), in
    the program's place, comes out as not correct at the seed's parameters
    where the program itself passes, on the same rows, with room on both
    sides of the tiny preset's limit."""
    control = spec.load_module("tools", "control").control
    config, family = _tiny_family()
    # On the CPU over four seeds of uniform rows, S=128: the leaves' median
    # error read 0.0120-0.0124 in the program (largest leaf 0.048-0.107) and
    # 0.102-0.109 in the control (largest leaf 0.41-0.50, over the 0.12 that
    # three times the limit allows).  The chip's readings at the cell's own
    # size set the configuration's own limit (its ``check_why``).
    limit = 0.04
    mesh = make_mesh(MeshSpec(), jax.local_devices()[:1])
    for seed in (0, 1):
        rows = np.random.default_rng(seed).integers(
            0, family.vocab_size, (1, 129)).astype(np.int32)
        program = correct.at_the_seed(family, mesh, seed, rows, limit)
        refused = correct.at_the_seed(control(family), mesh, seed, rows,
                                      limit)
        assert program["ok"], program
        assert not refused["ok"], refused
        assert 2 * program["grad_norm_err_median"] < limit \
            < refused["grad_norm_err_median"] / 2


# ------------------------------ (7) the other models' programs are untouched
#: sha256 of the text jax lowers each family's tiny train step to (no
#: locations in it), recorded on the parent of PR 40: the two halves of
#: ``llama._block`` moved into ``models/layers.py`` and ``models/moe.py``
#: learned a second scoring, a second activation and a shared expert without
#: one operation of these steps changing.  A change that means to alter one
#: of these programs records the new hash here and says so.  **PR 48 meant
#: to, in the four with experts**: what the router decided bears a name the
#: checkpoint keeps, the ids are ``top_k``'s of a value without a gradient
#: and the scores are read at them by a select and a sum (the forward's
#: numbers and, in float32, every gradient are the parent's to the bit:
#: ``tests/test_olmoe.py``, ``PERF.md`` PR 48); the two dense ones are the
#: parent's.  **PR 55 meant to, in the three whose expert layers hold a
#: share** (``tiny-sdar``, ``tiny-nemotron-h``, ``tiny-solar-open2``): the
#: window's combine gathers slot-major, (k, N, D), and sums over axis 0
#: (three steps of each on this CPU leave the parent's parameters and losses
#: to the bit; ``tests/test_moe_combine_layout.py``); ``tiny-olmoe`` holds
#: every expert and runs ``_combine``, untouched.
LOWERED_STEPS = {
    "tiny-llama":
        "f6d4a6b1cbf541233f13675bccbe7766fcb6a630709a7aa7b7f50a0428b1fe2c",
    "tiny-olmoe":
        "115daacfda98cd00748642ce33854899b338aef11544f9ea9a8dcadda6abf25f",
    "tiny-sdar":
        "a8918c30e97c45dcea988163b92a015cf348df7dec773fcc53019d75b7e138b6",
    "tiny-gpt2":
        "86135e6f43a576200ef38b5a76d717607699853ed5341b6350fd1c81db4dbe04",
    # the two hybrid presets, as PR 43 left them (added at PR 44: the tiny
    # Mamba-2 sizes lie off the chip's tiles and take ``ops.ssd.ssd_xla``,
    # whose lowered text is the parent's ``ssd``)
    "tiny-nemotron-h":
        "d911af43f10569b30e177e26e8d157abf9112a654b5409cbfe0bc5484e1f12c4",
    "tiny-solar-open2":
        "ba7f4d2464639435af96c62f55186c6f8fb59b856fd6788db50aa64331b4c1fa",
}


@pytest.mark.parametrize("name", sorted(LOWERED_STEPS))
def test_the_older_models_lower_to_the_parents_text(name):
    config = spec.load_json(spec.BENCH_DIR, "configs", name + ".json")
    family = spec.load_module("models", config["family"]).build(config, 128)
    optimizer = family.make_optimizer()
    params = jax.eval_shape(family.init_fn, jax.random.key(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = jax.jit(family.make_train_step(optimizer)).lower(
        params, opt_state, ids, ids).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED_STEPS[name]


# ------------------------------- (8) a kind is one entry of ``hybrid.KINDS``
#: sha256 over the leaves of each hybrid preset's parameters at key 0 (path,
#: dtype, shape, bytes; leaves in jax's order), recorded on the parent of
#: PR 46, where ``hybrid.init_params`` drew every kind's stack itself: the
#: kinds' modules draw from the same keys, so a cell's parameters stay the
#: function of ``--seed`` and ``init_seed`` its warm-up was fitted to
INIT_PARAMS = {
    "tiny-nemotron-h":
        "ebfc85ea46efc41f6519187746add9f4b03743061be81a10de24e0c863c87223",
    "tiny-solar-open2":
        "32dcf88a013bce5ad0b19cef861903d502cd126a2f676b45b698bcfd2b54a705",
}


@pytest.mark.parametrize("name", sorted(INIT_PARAMS))
def test_the_parameters_are_the_parents_bit_for_bit(name):
    config = spec.load_json(spec.BENCH_DIR, "configs", name + ".json")
    family = spec.load_module("models", config["family"]).build(config, 128)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            jax.jit(family.init_fn)(jax.random.key(0)))[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == INIT_PARAMS[name]


def _cell_config(name):
    """(the configuration a cell of the benchmark trains, its batch)."""
    cell = spec.load_cell(spec.load_benchmark(), name)
    config, traffic = cell["config_file"], cell["traffic_file"]
    model = spec.load_module("models", config["family"]).model_config(
        config, traffic["seq_len"])[1]
    return model, (traffic["seqs_per_chip"] * cell["chips"],
                   traffic["seq_len"])


#: ``hybrid._layer_sizes`` on the parent of PR 46, (q/k/v bytes, gate/up
#: bytes, the bound on the step's temporaries): what ``ops/remat.py`` decides
#: from.  The kinds' ``layer_bytes`` carry the parent's terms over as they
#: were, the two overstated ones with them (ROADMAP C15).  Behind them since
#: PR 48 what the expert layers' routing takes (``moe.routing_bytes`` a
#: layer), which the rule keeps whatever it decides: four layers each, of
#: 256 tokens with 16 experts and 2 a token, of 16,384 with 128 and 6, of
#: 8,192 with 320 and 8.
LAYER_SIZES = {
    "tiny": (lambda: (hybrid.HybridConfig.tiny(), (2, 128)),
             (131072, 262144, 7640128), 16 * (256 * (16 + 10) + 16)),
    "tiny_solar": (lambda: (hybrid.HybridConfig.tiny_solar(), (2, 128)),
                   (65536, 196608, 5602244), 16 * (256 * (16 + 10) + 16)),
    "nemotron-ep16-s8192": (lambda: _cell_config("nemotron-ep16-s8192"),
                            (150994944, 486539264, 9288687104),
                            16 * (16384 * (128 + 30) + 128)),
    "solar-open2-ep40-tp8": (lambda: _cell_config("solar-open2-ep40-tp8"),
                             (20971520, 167772160, 7130061200),
                             16 * (8192 * (320 + 40) + 320)),
}


@pytest.mark.parametrize("name", sorted(LAYER_SIZES))
def test_the_remat_rule_is_given_the_parents_sizes(name):
    build, (qkv, gate_up, temporaries), routing = LAYER_SIZES[name]
    config, (rows, seq_len) = build()
    shapes = jax.eval_shape(lambda: hybrid.init_params(config,
                                                       jax.random.key(0)))
    assert hybrid._layer_sizes(
        shapes, (rows, seq_len, config.d_model), config) == (
        [("attn_qkv", qkv), ("mlp_gate_up", gate_up),
         ("moe_routing", routing)], temporaries)


def _identity_kind():
    """A kind made here alone, as a module object with the interface of
    ``hybrid.KINDS``: ``x + scale * norm(x)``, one norm vector and one
    scale a layer, nothing for the ladder, no counter."""
    import types

    from ray_tpu.models.layers import rmsnorm

    def layer(config, axes, index):
        def mix(x, blk):
            with jax.named_scope("mlp"):
                return x + (rmsnorm(x, blk["id_norm"], config.rms_eps)
                            * blk["id_scale"]).astype(x.dtype), None
        return mix

    return types.SimpleNamespace(
        init_params=lambda config, key, n, out_std: {
            "id_norm": jnp.ones((n, config.d_model)),
            "id_scale": jax.random.normal(key, (n, 1)) * out_std},
        logical_axes=lambda config: {"id_norm": ("layers", "norm"),
                                     "id_scale": ("layers", None)},
        matmul_params=lambda config, routed: 0,
        num_params=lambda config: config.d_model + 1,
        mixer_flops=lambda config, seq_len: 0.0,
        layer_bytes=lambda config, tokens, seq_len, tensor, itemsize: (
            2 * tokens * config.d_model * itemsize, 0, {}),
        first_call_facts=lambda config, rows, seq_len: {"id_layers":
                                                        config.count("I")},
        layer=layer)


def test_a_kind_is_a_module_and_one_line_of_kinds(monkeypatch):
    """The seam's own test: a kind registered from outside initialises,
    shards, counts its parameters and FLOPs, is sized for the remat rule,
    notes its facts and trains a step beside the others, with no edit to
    ``models/hybrid.py``."""
    monkeypatch.setitem(hybrid.KINDS, "I",
                        hybrid.Kind("identity", _identity_kind(), 6))
    plain = dataclasses.replace(hybrid.HybridConfig.tiny(), attn_impl="xla",
                                pattern="ME*E")
    config = dataclasses.replace(plain, pattern="MIE*IE")
    params = hybrid.init_params(config, jax.random.key(0))
    assert params["identity"]["id_norm"].shape == (2, config.d_model)
    # the others draw from the keys they drew from without it: the same
    # leaves, each layer's last matrix rescaled for six layers and not four
    rest = {k: v for k, v in params.items() if k != "identity"}
    assert jax.tree.all(jax.tree.map(
        lambda a, b: np.array_equal(a, b) or np.allclose(
            a * np.sqrt(6 / 4), b, rtol=1e-6, atol=0),
        rest, hybrid.init_params(plain, jax.random.key(0))))
    axes = hybrid.logical_axes(config)
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(a, tuple)) \
        == jax.tree.structure(params)
    assert hybrid.num_params(config) == sum(
        a.size for a in jax.tree.leaves(params)) \
        == hybrid.num_params(plain) + 2 * (config.d_model + 1)
    assert hybrid.flops_per_token(config) == hybrid.flops_per_token(plain)
    mesh = make_mesh(MeshSpec(data=2), jax.devices()[:2])
    optimizer = hybrid.make_optimizer()
    state, opt_state = create_sharded_state(
        lambda key: hybrid.init_params(config, key), axes, mesh,
        jax.random.key(0), optimizer)
    step_fn = hybrid.make_train_step(config, optimizer)
    step = jit_train_step(step_fn, mesh=mesh)
    ids = np.random.default_rng(0).integers(0, 1024, (2, 129)).astype(
        np.int32)
    device_telemetry.reset()
    moved, _, loss = step(state, opt_state, ids[:, :-1], ids[:, 1:])
    assert np.isfinite(float(loss))
    assert not np.array_equal(moved["identity"]["id_scale"],
                              params["identity"]["id_scale"])
    (row,) = device_telemetry.first_calls("train_step")
    assert row["layer_kinds"] == "MIE*IE" and row["id_layers"] == 2
    # the expert layers' counters: layers, batch shards, held experts
    assert step_fn.counters["moe_rows"].shape == (2, 2, 4)


def test_importing_llama_loads_no_state_space_module():
    """``ops/ssd.py`` and ``models/mamba2.py`` load when a hybrid decoder
    with ``M`` in its pattern is built, not with ``ray_tpu`` or
    ``ray_tpu.models.llama``."""
    script = ("import sys, ray_tpu, ray_tpu.models.llama\n"
              "late = {'ray_tpu.ops.ssd', 'ray_tpu.models.mamba2', "
              "'ray_tpu.models.hybrid'}\n"
              "assert not late & set(sys.modules), late & set(sys.modules)\n"
              "from ray_tpu.models import hybrid\n"
              "hybrid.num_params(hybrid.HybridConfig.tiny())\n"
              "assert late <= set(sys.modules)\n")
    done = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, env={"JAX_PLATFORMS": "cpu",
                                          "PATH": "/usr/bin:/bin"},
                          cwd=spec.ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
