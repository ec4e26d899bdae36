"""NVIDIA Nemotron-3-Nano through ``models/hybrid.py``: a stack of Mamba-2
(``models/mamba2.py`` over ``ops/ssd.py``), attention and expert layers
(``models/layers.py``, ``models/moe.py`` with sigmoid scores, a selection
bias, two-matrix relu^2 experts and a shared expert).  What every family is
held to is ``tests/test_families.py``'s, by the row ``nemotron_h``.

The plain reference is ``benchmarks/reference/nemotron_h.py``, the one copy
(float32, the recurrence position by position, every held expert applied to
every position).  Everything runs on the CPU with seeded random weights at
tiny sizes, attention on the einsum path; the grouped matmul has no other
path than its kernel in interpret mode.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.reference import nemotron_h as reference
from ray_tpu.models import experts, hybrid, mamba2, moe
from ray_tpu.ops.ssd import ssd
from ray_tpu.parallel import MeshSpec, make_mesh
from ray_tpu.parallel.train_state import (create_sharded_state,
                                          jit_train_step)
from ray_tpu.util import device_telemetry
from tests import families
from tests.families import rel_err


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
        assert rel_err(got, want) < 1e-5
        dy = jax.random.normal(jax.random.key(9), want.shape)
        (grads,), (grads_ref,) = vjp(dy), vjp_ref(dy)
    for name in a:
        assert np.all(np.isfinite(grads[name])), name
        # float32 sums in another order; A_log's is one sum over everything
        assert rel_err(grads[name], grads_ref[name]) < 1e-3, name


def test_scan_products_are_in_the_inputs_dtype():
    """bf16 in: bf16 products with float32 accumulation, within bf16's
    rounding of the float32 recurrence."""
    a = _scan_inputs(3)
    low = dict(a, **{k: a[k].astype(jnp.bfloat16) for k in ("x", "B", "C")})
    got = _chunked(low, 8)
    assert got.dtype == jnp.bfloat16
    assert rel_err(got, _position_by_position(a)) < 0.05


# ------------------------------------------------------------- (2) the mixer
def _mixer_parts(dtype=jnp.float32):
    config = families.preset("nemotron_h", dtype=dtype)
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
    assert rel_err(got, want) < 1e-5


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
    config = families.float32("nemotron_h")
    before = [experts.router_bias(config, i) for i in range(4)]
    assert all(b.shape == (config.n_experts,) and np.any(b) for b in before)
    assert not np.allclose(before[0], before[1])  # a draw a layer
    params = hybrid.init_params(config, jax.random.key(0))
    sizes = {a.shape for a in jax.tree.leaves(params["experts"])}
    assert (config.count("E"), config.n_experts) not in sizes
    optimizer = optax.adamw(1e-2)
    step = jax.jit(hybrid.make_train_step(config, optimizer))
    tokens, targets = families.rows(config.vocab_size)
    moved, _, loss = step(params, optimizer.init(params), tokens, targets)
    assert np.isfinite(float(loss))
    assert all(not np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(moved)))
    after = [experts.router_bias(config, i) for i in range(4)]
    assert all(np.array_equal(a, b) for a, b in zip(before, after))


def test_the_bias_changes_some_of_the_choices():
    """At the tiny preset's spread the bias changes some tokens' experts and
    leaves others': the mechanism is no no-op and does not take over."""
    config, family = families.family("nemotron_h", "float32")
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
    assert rel_err(total.reshape(-1, D), want) < 1e-5


# ------------------------------- (5) a kind is one entry of ``hybrid.KINDS``
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
            2 * tokens * config.d_model * itemsize, 0,
            {"id_scaled": (tokens * config.d_model * itemsize, 1.0)}),
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
    plain = families.preset("nemotron_h", attn_impl="xla", pattern="ME*E")
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
    tokens, targets = families.rows(config.vocab_size)
    device_telemetry.reset()
    moved, _, loss = step(state, opt_state, tokens, targets)
    assert np.isfinite(float(loss))
    assert not np.array_equal(moved["identity"]["id_scale"],
                              params["identity"]["id_scale"])
    (row,) = device_telemetry.first_calls("train_step")
    assert row["layer_kinds"] == "MIE*IE" and row["id_layers"] == 2
    # the expert layers' counters: layers, batch shards, held experts
    assert step_fn.counters["moe_rows"].shape == (2, 2, 4)
