"""poolside's Laguna through ``models/hybrid.py``: two kinds of attention of
different head counts in one stack, full (``*``: the head's first lanes
rotating under YaRN) and over a causal band (``W``, ``models/window.py``:
the whole head rotating plainly), each with an output gate a head, a leading
dense layer and SwiGLU experts beside a shared expert, for one chip's share
of the experts.

The plain reference is ``benchmarks/reference/laguna.py``, the one copy
(float32, masks as boolean arrays, the rotary by sliced halves, a loop over
the held experts).  Everything runs on the CPU with seeded random weights at
tiny sizes, attention on the einsum path unless a test says otherwise.  What
every family is held to is ``tests/test_families.py``'s, by the row
``laguna``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec
from benchmarks.reference import laguna as reference
from benchmarks.reference.llama import _rmsnorm
from ray_tpu.models import attn, hybrid, moe, window
from ray_tpu.models.layers import Yarn, attention as attention_half, rope
from ray_tpu.ops import attention, remat
from ray_tpu.util import first_call
from tests import families
from tests.families import rel_err

FAMILY = "laguna"

#: the benchmark's configurations, each with a rehearsal preset
CONFIGS = [c["name"] for c in spec.load_benchmark()["configs"]]


def _allowed_by(run, S):
    """(S, S) booleans: the keys ``run(q, k, v)`` lets each query read.  With
    q = k = 0 every allowed key of a query gets the same weight, and with v
    the identity the output row is those weights."""
    zeros = jnp.zeros((1, S, 2, 32), jnp.float32)
    v = jnp.broadcast_to(jnp.eye(S, dtype=jnp.float32)[None, :, None, :],
                         (1, S, 2, S))
    out = np.asarray(run(zeros, zeros, v))
    assert np.array_equal(out[0, :, 0], out[0, :, 1])
    return out[0, :, 0] > 0


@pytest.mark.parametrize("w", [1, 96, 128, 200, 384, 1000],
                         ids=lambda w: f"w{w}")
@pytest.mark.parametrize("impl", ["xla", "splash"])
def test_the_band_is_the_keys_up_to_the_querys_own(impl, w, monkeypatch):
    """``window_attention`` allows key j for query i when ``j <= i and j > i
    - w``, pair by pair: on the einsum path and in the splash kernel
    (interpret mode, blocks of 128 over a row of 384), at bands narrower
    than a block, a block wide, over a block, the whole row and wider."""
    S = 384
    if impl == "splash":
        real = attention._splash_kernel
        monkeypatch.setattr(
            attention, "_splash_kernel", lambda *a, **k: real(
                *a, blocks=attention.SplashBlocks.square(128), **k))
    got = _allowed_by(lambda q, k, v: attention.window_attention(
        q, k, v, w, impl), S)
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    assert np.array_equal(got, (j <= i) & (j > i - w))
    assert np.array_equal(got, np.asarray(attention.window_allowed(i, j, w)))
    assert np.array_equal(got, np.asarray(reference.allowed(
        jnp.arange(S), jnp.arange(S), w)))
    # a query reads w keys once the row has them; the band's area
    assert got.sum() == S * min(w, S) - min(w, S) * (min(w, S) - 1) // 2


@pytest.mark.parametrize("blocks", [
    attention.SplashBlocks(128, 128, 128, 128, 128, 128),
    attention.SplashBlocks(128, 256, 128, 256, 128, 128),
    attention.SplashBlocks(256, 128, 128, 128, 256, 128),
], ids=["square", "unequal", "unequal-the-other-way"])
def test_the_bands_kernel_agrees_with_the_einsum(blocks, monkeypatch):
    """Interpret mode, a band of 160 keys over a row of 512, GQA at the
    window kind's 6 heads over 2: output and the three gradients against the
    einsum path; the call's facts go by the band's names and count the pairs
    inside the blocks it runs."""
    B, S, H, KV, hd, w = 1, 512, 6, 2, 32, 160
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k, v = (jax.random.normal(key, (B, S, KV, hd)) for key in ks[1:3])
    do = jax.random.normal(ks[3], q.shape)
    real = attention._splash_kernel
    monkeypatch.setattr(attention, "_splash_kernel",
                        lambda *a, **kw: real(*a, blocks=blocks, **kw))

    def run(impl):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(attention.window_attention(
                q, k, v, w, impl) * do), argnums=(0, 1, 2)))(q, k, v)

    with first_call.noting() as notes:
        (a, ga), (b, gb) = run("xla"), run("splash")
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    for x, y in zip(ga, gb):
        assert float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x)) < 1e-5
    assert "attn_blocks" not in notes
    assert notes["window_blocks"] == (blocks.q, blocks.kv, blocks.q_bwd,
                                      blocks.kv_bwd)
    # blocks with work: those a band of 160 keys below the diagonal touches
    i = np.arange(S // blocks.q)[:, None] * blocks.q
    j = np.arange(S // blocks.kv)[None, :] * blocks.kv
    touched = (j <= i + blocks.q - 1) & (j + blocks.kv - 1 > i - w)
    assert notes["window_blocks_run"] == touched.sum()
    assert notes["window_pairs_visited"] \
        == touched.sum() * blocks.q * blocks.kv >= S * w - w * (w - 1) // 2


def test_the_rule_reads_the_band():
    """``splash_blocks`` with a window: the accepted cells' calls keep their
    blocks, and a band narrower than the wide block does not run in it."""
    assert attention.splash_blocks(8192, 128) \
        == attention.splash_blocks(8192, 128, 0) \
        == attention.SplashBlocks(1024, 1024, 512, 1024, 1024, 512)
    band = attention.splash_blocks(8192, 128, 512)
    assert band == attention.SplashBlocks(512, 512, 512, 512, 1024, 512)
    # a band as wide as the row is the triangle, and runs in its blocks
    assert attention.splash_blocks(8192, 128, 8192) \
        == attention.splash_blocks(8192, 128)


# ------------------------------------------------------- (2) the rotary pass
YARN = Yarn(factor=4.0, original=64, beta_fast=4.0)


def _rope_parameters(rotary, hd, theta, yarn):
    if yarn is None:
        return {"rope_type": "default", "rope_theta": theta,
                "partial_rotary_factor": rotary / hd}
    return {"rope_type": "yarn", "rope_theta": theta,
            "partial_rotary_factor": rotary / hd, "factor": yarn.factor,
            "original_max_position_embeddings": yarn.original,
            "beta_fast": yarn.beta_fast, "beta_slow": yarn.beta_slow,
            "attention_factor": yarn.scale}


@pytest.mark.parametrize("rotary,yarn", [
    (16, YARN), (32, YARN), (16, None), (8, dataclasses.replace(
        YARN, attention_factor=1.4852030263919618))],
    ids=["half-yarn", "whole-yarn", "half-plain", "quarter-yarn-stated"])
def test_rope_over_the_first_lanes_and_a_yarn_table(rotary, yarn):
    """``layers.rope`` in one pass over heads of 32 lanes with the first
    ``rotary`` rotating, under YaRN's table and scale or plainly, forward and
    backward against the reference's sliced halves (which makes its table
    from the formulas itself); the lanes after the rotary part pass
    unrotated and unscaled, and the backward is the rotation back."""
    theta, hd = 10000.0, 32
    ks = jax.random.split(jax.random.key(rotary), 2)
    x = jax.random.normal(ks[0], (2, 80, 3, hd))
    do = jax.random.normal(ks[1], x.shape)
    table = yarn and yarn.inv_freq(rotary, theta)
    scale = yarn.scale if yarn else 1.0

    def ours(x):
        return rope(x, theta, rotary, False, True, table, scale)

    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(lambda x: reference.rotary(
            x, _rope_parameters(rotary, hd, theta, yarn)), x)
        got, ours_pull = jax.vjp(ours, x)
        assert rel_err(got, want) < 1e-5
        assert rel_err(ours_pull(do)[0], pull(do)[0]) < 1e-5
        # the backward is the rotation back, scaled as the forward is
        assert rel_err(ours_pull(got)[0][..., :rotary],
                        x[..., :rotary] * scale ** 2) < 1e-5
    assert np.array_equal(got[..., rotary:], x[..., rotary:])
    assert np.allclose(jnp.linalg.norm(got[..., :rotary], axis=-1),
                       scale * jnp.linalg.norm(x[..., :rotary], axis=-1),
                       rtol=1e-5)


def test_the_yarn_table_is_the_formulas():
    """``Yarn.inv_freq`` against the reference's own table at the published
    settings (64 lanes, base 5e5, factor 128 over 8192): the first pairs
    keep their frequency, the last are stretched 128 times, the ramp between
    runs from pair 9 to pair 18; the scale is ``0.1 ln(factor) + 1``."""
    full = {"rope_theta": 500000, "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32}
    yarn = Yarn(factor=128.0, original=8192)
    ours = np.asarray(yarn.inv_freq(64, 500000.0), np.float32)
    want = np.asarray(reference.yarn_inv_freq(64, full))
    assert ours.shape == (32,) and np.allclose(ours, want, rtol=2e-6)
    plain = 1.0 / 500000.0 ** (np.arange(0, 64, 2) / 64)
    assert np.allclose(ours[:10], plain[:10], rtol=1e-5)
    assert np.allclose(ours[18:], plain[18:] / 128, rtol=1e-5)
    assert np.all(np.diff(ours) < 0)
    assert yarn.scale == pytest.approx(1.4852030263919618)
    assert Yarn(2.0, 64, attention_factor=1.25).scale == 1.25


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_factor_of_one_over_all_lanes_is_todays_rope(dtype):
    """The new arguments at their plain values give the pass the older
    models run, bit for bit: all lanes ``first``, a scale of 1.  A YaRN
    table of factor 1 holds every frequency at its own, to the float32
    rounding of a table made in numpy beside XLA's ``theta ** x`` (an ulp of
    a frequency: not bit for bit, which no table made off the device is)."""
    x = jax.random.normal(jax.random.key(0), (2, 64, 3, 32)).astype(dtype)
    today = jax.jit(lambda x: rope(x, 10000.0))(x)
    assert np.array_equal(today, jax.jit(
        lambda x: rope(x, 10000.0, None, False, True))(x))
    assert np.array_equal(today, jax.jit(
        lambda x: rope(x, 10000.0, 32, False, True, None, 1.0))(x))
    table = Yarn(factor=1.0, original=64).inv_freq(32, 10000.0)
    plain = 1.0 / 10000.0 ** (np.arange(0, 32, 2) / 32)
    assert np.allclose(table, plain, rtol=3e-7)
    with_table = jax.jit(
        lambda x: rope(x, 10000.0, 32, False, True, table, 1.0))(x)
    assert rel_err(with_table, today) < (1e-5 if dtype == "float32"
                                          else 1e-2)


# ------------------------------------------------------ (3) the gate a head
@pytest.mark.parametrize("kind", ["*", "W"])
def test_the_gate_is_one_scalar_a_head(kind):
    """``wg`` is D x H and ``wo`` reads ``o_head * sigmoid(h wg)_head``: the
    layer against the reference's mixer in float32, output and gradients of
    every leaf and the input, on the full kind (4 heads, half-rotary YaRN)
    and on the window kind (6 heads, 16 keys, the whole head rotating)."""
    config = families.float32(FAMILY)
    module = hybrid.KINDS[kind].module
    blk = jax.tree.map(lambda a: a[0] * 6.0, module.init_params(
        config, jax.random.key(0), 1, 0.02))
    blk["attn_norm"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.key(1), blk["attn_norm"].shape)
    heads = {"*": config.n_head, "W": config.window_heads}[kind]
    assert blk["wg"].shape == (config.d_model, heads)
    x = jax.random.normal(jax.random.key(2), (2, 64, config.d_model))
    do = jax.random.normal(jax.random.key(3), x.shape)
    cfg, _ = families.family(FAMILY, "float32")
    full, sliding = (cfg["rope_parameters"][t] for t in (
        "full_attention", "sliding_attention"))
    ref_cfg = {
        "layer_types": ["sliding_attention" if kind == "W"
                        else "full_attention"],
        "num_attention_heads_per_layer": [heads], "num_key_value_heads": 2,
        "head_dim": config.head_dim, "sliding_window": config.window_keys,
        "gating_types": ["per_head"],
        "rope_parameters": {
            "full_attention": dict(
                full, rope_theta=config.rope_theta,
                factor=config.rope_yarn.factor,
                original_max_position_embeddings=config.rope_yarn.original,
                beta_fast=config.rope_yarn.beta_fast,
                attention_factor=config.rope_yarn.scale),
            "sliding_attention": sliding}}
    layer = module.layer(config, module.logical_axes(config), 0)

    def ours(blk, x):
        return jnp.sum(layer(x, blk)[0] * do)

    def written_out(blk, x):
        h = _rmsnorm(x, blk["attn_norm"], config.rms_eps)
        return jnp.sum((x + reference.attention(h, blk, ref_cfg, 0, 32)) * do)

    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(ours, (0, 1)))(blk, x)
        want, ref_grads = jax.jit(jax.value_and_grad(written_out, (0, 1)))(
            blk, x)
    assert rel_err(got, want) < 1e-5
    errors = jax.tree.map(rel_err, grads, ref_grads)
    assert set(errors[0]) == {"attn_norm", "wq", "wk", "wv", "wg", "wo"}
    for path, err in jax.tree_util.tree_flatten_with_path(errors)[0]:
        assert err < 2e-4, (jax.tree_util.keystr(path), err)


def test_one_attention_function_serves_both_kinds():
    """``models/window.py`` holds no layer of its own: its functions are
    ``models/attn.py``'s over the configuration ``as_attention`` gives, and
    both kinds' layers are ``layers.attention``."""
    config = families.float32(FAMILY)
    view = window.as_attention(config)
    assert (view.n_head, view.attn_window, view.rope_theta,
            view.rope_rotary, view.rope_yarn) == (6, 16, 10000.0, None, None)
    assert (config.n_head, config.attn_window) == (4, 0)
    assert window.as_attention(config) is view  # made once a configuration
    assert window.num_params(config) == attn.num_params(view) \
        == 64 * 16 * (2 * 6 + 2 * 2) + 64 * 6 + 64
    # charged the band's pairs, not the triangle's
    assert window.mixer_flops(config, 128) < attn.mixer_flops(config, 128) / 2
    assert window.layer.__wrapped__ is attn.layer
    source = open(window.__file__).read()
    assert "def layer" not in source and "named_scope" not in source
    assert attention_half.__module__ == "ray_tpu.models.layers"


# -------------------------------------------- (4) the share ties to the model
def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The expert layer's output over the shares [0, 4), [4, 8), [8, 12),
    [12, 16) of the tiny preset's 16 experts, the shared expert counted
    once, sums to the reference's layer with every expert held."""
    from ray_tpu.models import experts

    whole = families.float32(FAMILY, experts_held=None)
    blk = jax.tree.map(lambda a: a[0], experts.init_params(
        whole, jax.random.key(0), 1, 0.02))
    blk["router"] = blk["router"] * 20.0
    x = jax.random.normal(jax.random.key(1), (2, 64, whole.d_model))
    ref_cfg = {"experts_held": [0, 16], "num_experts_per_tok": 2,
               "norm_topk_prob": True, "moe_routed_scaling_factor": 2.5,
               "router_scoring": "sigmoid"}

    def part(first, stop):
        config = families.float32(FAMILY, experts_held=range(first, stop))
        held = dict(blk, **{name: blk[name][first:stop]
                            for name in ("w_gate", "w_up", "w_down")})
        layer = experts.layer(config, experts.logical_axes(config), 0)
        return layer(x, held)[0] - x

    def shared_alone():
        h = _rmsnorm(x, blk["mlp_norm"], whole.rms_eps)
        return reference.swiglu(h, blk["shared_gate"], blk["shared_up"],
                                blk["shared_down"])

    with jax.default_matmul_precision("highest"):
        parts = [part(first, first + 4) for first in range(0, 16, 4)]
        shared = shared_alone()
        h = _rmsnorm(x, blk["mlp_norm"], whole.rms_eps).reshape(128, -1)
        want = reference.experts(h, blk, ref_cfg).reshape(x.shape)
    assert rel_err(sum(parts) - 3 * shared, want) < 1e-4
    assert all(rel_err(p, want) > 0.05 for p in parts)  # no share is all


# ------------------------------------------------------ (5) the whole model
def test_the_softmax_reading_of_the_router_is_one_key():
    """``router_scoring`` is the configuration's statement of an assumption:
    under ``softmax`` the program and the reference still agree, and the
    loss is another."""
    losses = {}
    for scoring in ("sigmoid", "softmax"):
        _, family = families.family(
            FAMILY, "float32", router_scoring=scoring)
        params = jax.jit(family.init_fn)(jax.random.key(0))
        params["experts"]["router"] = params["experts"]["router"] * 8.0
        rows = np.random.default_rng(1).integers(
            0, family.vocab_size, (1, 129)).astype(np.int32)
        ours = jax.jit(family.loss_fn)(params, rows[:, :-1], rows[:, 1:])
        want = jax.jit(lambda p, t, y: family.reference_loss(p, t, y, 64))(
            params, rows[:, :-1], rows[:, 1:])
        assert rel_err(ours, want) < 1e-5
        losses[scoring] = float(ours)
    assert abs(losses["sigmoid"] - losses["softmax"]) > 1e-4


def test_the_remat_rule_is_given_the_window_kinds_sizes():
    """``_layer_sizes`` counts q, k and v of the window layers at their own
    head count beside the full layers'."""
    config = families.preset(FAMILY, attn_impl="xla")
    shapes = jax.eval_shape(lambda: hybrid.init_params(
        config, jax.random.key(0)))
    candidates, _ = hybrid._layer_sizes(shapes, (2, 128, config.d_model),
                                        config)
    tokens = 256
    # heads of 16: q's and twice the two key heads', bf16; a dense layer of
    # 128 beside four shared experts of 48
    assert families.named(candidates) == {
        remat.QKV: tokens * 16 * 2 * (2 * (4 + 4) + 3 * (6 + 4)),
        remat.GATE_UP: tokens * 2 * 2 * (4 * 48 + 128),
        remat.ROUTING: 4 * moe.routing_bytes(tokens, 16, 2)}


# ------------------------- (6) the yardstick's mask areas, the tier-1 copy
def _allowed_by_the_program(kind_of_mask, S, **how):
    n = 2 * S if kind_of_mask == "block_diffusion" else S
    zeros = jnp.zeros((1, n, 1, 8), jnp.float32)
    v = jnp.eye(n, dtype=jnp.float32)[None, :, None, :]
    if kind_of_mask == "block_diffusion":
        out = attention.block_diffusion_attention(
            zeros, zeros, v, how["block_length"], "xla")
    elif kind_of_mask == "window":
        out = attention.window_attention(zeros, zeros, v, how["window"],
                                         "xla")
    else:
        out = attention.causal_attention(zeros, zeros, v, "xla")
    return np.asarray(out[0, :, 0, :]) > 0


@pytest.mark.parametrize("name", CONFIGS)
def test_a_kinds_pairs_are_the_pairs_the_programs_mask_allows(name):
    """Each kind of a family's ``attention_calls`` charges ``pairs(S)`` a
    head, and the mask the program's own attention builds allows just those
    pairs (a causal call at ``lib/cost.py``'s convention: half the square,
    the diagonal's other half left out), over the rehearsal preset of every
    configuration of the benchmark."""
    S = 64
    held = spec.load_json(spec.BENCH_DIR, "configs", name + ".json")
    tiny = spec.load_json(spec.BENCH_DIR, "configs",
                          held["rehearse_with"] + ".json")
    calls = {c.name: c for c in spec.load_module(
        "models", tiny["family"]).build(tiny, S).attention_calls}
    if "block_length" in tiny:
        allowed = _allowed_by_the_program(
            "block_diffusion", S, block_length=tiny["block_length"])
        whole, noised, clean = calls.values()
        assert allowed.sum() == whole.pairs(S)
        assert allowed[:S].sum() == noised.pairs(S)
        assert allowed[S:, S:].sum() == clean.pairs(S)
        return
    triangle = _allowed_by_the_program("causal", S)
    assert np.array_equal(triangle, np.tril(np.ones((S, S), bool)))
    assert triangle.sum() - calls["causal"].pairs(S) == S / 2
    if "window" in calls:
        band = _allowed_by_the_program("window", S,
                                       window=tiny["sliding_window"])
        assert set(calls) == {"causal", "window"}
        assert band.sum() == calls["window"].pairs(S)  # exact
        assert calls["window"].q_heads != calls["causal"].q_heads
    else:
        assert set(calls) == {"causal"}
