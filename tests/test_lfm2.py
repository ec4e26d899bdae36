"""Liquid AI's LFM2-8B-A1B through ``models/hybrid.py``: a gated short
convolution as the token mixer of most layers (kind ``C``,
``models/shortconv.py``), rotary GQA with an RMSNorm a head on q and k on the
others, a leading dense layer, SwiGLU experts under a selection bias with no
shared expert, a tied head, for one chip's share of the experts.

The plain reference is ``benchmarks/reference/lfm2_moe.py``, the one copy
(float32, the convolution as three shifted multiply-adds, attention a block
of queries at a time, a loop over the held experts).  Everything runs on the
CPU with seeded random weights at tiny sizes, attention on the einsum path.
What every family is held to is ``tests/test_families.py``'s, by the row
``lfm2_moe``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmarks.lib import spec
from benchmarks.reference import lfm2_moe as reference
from benchmarks.reference.llama import _rmsnorm
from ray_tpu.models import attn, hybrid, mamba2, shortconv
from tests import families
from tests.families import rel_err

FAMILY = "lfm2_moe"


def _conv_layer(config, seed=0):
    """(one layer's parameters, the kind's layer function)."""
    blk = jax.tree.map(lambda a: a[0], shortconv.init_params(
        config, jax.random.key(seed), 1, 0.02))
    blk["conv_norm"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.key(seed + 1), blk["conv_norm"].shape)
    return blk, shortconv.layer(config, shortconv.logical_axes(config), 0)


# ------------------------------------------- (1) the kind ``C``, by itself
@pytest.mark.parametrize("positions", [1, 2, 3, 33])
def test_the_mixer_is_the_references_three_shifted_multiply_adds(positions):
    """Forward and every gradient of a ``C`` layer against the reference's
    mixer under the same pre-norm, on rows of one position (two of the three
    taps read zeros), of fewer positions than taps and of many."""
    config = families.float32(FAMILY)
    blk, layer = _conv_layer(config)
    x = jax.random.normal(jax.random.key(2), (3, positions, config.d_model))

    def ours(x, blk):
        return layer(x, blk)[0]

    def theirs(x, blk):
        return x + reference.short_conv(
            _rmsnorm(x, blk["conv_norm"], config.rms_eps), blk)

    probe = jax.random.normal(jax.random.key(3), x.shape)
    with jax.default_matmul_precision("highest"):
        assert rel_err(ours(x, blk), theirs(x, blk)) < 1e-5
        got, want = (jax.grad(lambda x, blk: jnp.sum(f(x, blk) * probe),
                              argnums=(0, 1))(x, blk)
                     for f in (ours, theirs))
    for path, err in jax.tree_util.tree_flatten_with_path(
            jax.tree.map(rel_err, got, want))[0]:
        assert err < 2e-5, (jax.tree_util.keystr(path), err)


def test_the_first_positions_see_zeros_and_no_later_position():
    """Causal, and nothing before the row: position t's output reads
    positions t-2, t-1, t of its own row and no other; a row's first
    position is the last tap alone."""
    width = 8
    bcu = jax.random.normal(jax.random.key(0), (2, 6, 3 * width))
    w = jax.random.normal(jax.random.key(1), (3, width))
    B, C, u = np.split(np.asarray(bcu), 3, axis=-1)
    z = B * u
    out = np.asarray(shortconv.gated_conv(bcu, w))
    np.testing.assert_allclose(out[:, 0], C[:, 0] * w[2] * z[:, 0],
                               rtol=1e-5)
    np.testing.assert_allclose(
        out[:, 1], C[:, 1] * (w[1] * z[:, 0] + w[2] * z[:, 1]), rtol=1e-5)
    # a change at position 3 reaches positions 3, 4, 5 of its row alone
    moved = np.asarray(shortconv.gated_conv(bcu.at[0, 3].add(1.0), w))
    changed = np.any(moved != out, axis=-1)
    assert changed.tolist() == [[False, False, False, True, True, True],
                                [False] * 6]


def test_the_pass_keeps_its_input_and_the_taps_alone():
    """``gated_conv``'s residuals are ``[B | C | u]`` as the projection wrote
    it and the taps: the product and the taps' sum are made again in the
    backward, not kept in float32."""
    bcu = jnp.ones((2, 16, 3 * 8), jnp.bfloat16)
    w = jnp.ones((3, 8), jnp.float32)
    _, pull = jax.vjp(shortconv.gated_conv, bcu, w)
    kept = sorted((a.dtype.name, a.shape) for a in jax.tree.leaves(pull)
                  if hasattr(a, "shape") and a.size > 1)
    assert kept == [("bfloat16", (2, 16, 24)), ("float32", (3, 8))]
    # in bfloat16 the output is rounded once, from float32
    bcu = jax.random.normal(jax.random.key(0), (2, 16, 24)).astype(
        jnp.bfloat16)
    B, C, u = (np.asarray(a, np.float32)
               for a in jnp.split(bcu, 3, axis=-1))
    padded = np.pad(B * u, ((0, 0), (2, 0), (0, 0)))
    want = C * sum(padded[:, j:j + 16] for j in range(3))
    got = shortconv.gated_conv(bcu, w)
    assert got.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(jnp.asarray(want).astype(jnp.bfloat16),
                                     np.float32))


# ------------------------------ (2) ``causal_conv`` without a bias, any taps
@pytest.mark.parametrize("taps", [1, 3, 4])
def test_causal_conv_without_a_bias_is_laxs_convolution(taps):
    """``mamba2.causal_conv(x, w, None)`` at any number of taps against
    ``lax.conv_general_dilated`` (depthwise, padded on the left), forward
    and both gradients; with a bias the function is what it was."""
    x = jax.random.normal(jax.random.key(0), (2, 11, 6))
    w = jax.random.normal(jax.random.key(1), (taps, 6))

    def by_lax(x, w):
        return lax.conv_general_dilated(
            x, w[:, None, :], window_strides=(1,), padding=[(taps - 1, 0)],
            dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=6,
            precision=lax.Precision.HIGHEST)

    probe = jax.random.normal(jax.random.key(2), x.shape)
    assert rel_err(mamba2.causal_conv(x, w, None), by_lax(x, w)) < 1e-5
    got, want = (jax.grad(lambda x, w: jnp.sum(f(x, w) * probe),
                          argnums=(0, 1))(x, w)
                 for f in (lambda x, w: mamba2.causal_conv(x, w, None),
                           by_lax))
    assert rel_err(got[0], want[0]) < 1e-5
    assert rel_err(got[1], want[1]) < 1e-5
    b = jax.random.normal(jax.random.key(3), (6,))
    assert rel_err(mamba2.causal_conv(x, w, b), by_lax(x, w) + b) < 1e-5
    db = jax.grad(lambda b: jnp.sum(mamba2.causal_conv(x, w, b) * probe))(b)
    assert rel_err(db, jnp.sum(probe, axis=(0, 1))) < 1e-5


# ----------------------------------------------------- (3) the tied head
def test_the_tied_heads_embedding_gradient_is_the_gathers_plus_the_heads():
    """With ``tie_head`` no ``lm_head`` leaf exists, and ``wte``'s gradient
    is the sum of what the untied model, its head set to the embedding,
    gives ``wte`` (the gather's) and ``lm_head`` (the head's)."""
    tied = families.float32(FAMILY, pattern="CD*E")
    untied = dataclasses.replace(tied, tie_head=False)
    params = hybrid.init_params(tied, jax.random.key(0))
    assert "lm_head" not in params
    assert "lm_head" not in hybrid.logical_axes(tied)
    both = dict(params, lm_head=params["wte"])
    assert jax.tree.structure(both) == jax.tree.structure(
        jax.eval_shape(lambda: hybrid.init_params(untied, jax.random.key(0))))
    assert hybrid.num_params(untied) - hybrid.num_params(tied) \
        == tied.vocab_size * tied.d_model
    assert hybrid.flops_per_token(untied) == hybrid.flops_per_token(tied)
    rows = np.random.default_rng(0).integers(0, 1024, (2, 65)).astype(
        np.int32)
    tokens, targets = rows[:, :-1], rows[:, 1:]
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(hybrid.loss_fn)(
            params, tokens, targets, tied)
        loss2, grads2 = jax.value_and_grad(hybrid.loss_fn)(
            both, tokens, targets, untied)
    assert float(loss) == pytest.approx(float(loss2), rel=1e-6)
    assert rel_err(grads["wte"], grads2["wte"] + grads2["lm_head"]) < 1e-5
    # neither part alone is the gradient
    assert rel_err(grads["wte"], grads2["wte"]) > 0.1
    assert rel_err(grads["wte"], grads2["lm_head"]) > 0.01


# -------------------------------------- (4) attention's QK-norm on this path
def test_the_attention_kind_norms_q_and_k_a_head():
    """``qk_norm="head"`` gives the kind one weight of ``head_dim`` for q and
    one for k a layer, counted in ``num_params``; without it the kind's
    parameters are what they were."""
    config = families.float32(FAMILY)
    plain = dataclasses.replace(config, qk_norm=False)
    params = attn.init_params(config, jax.random.key(0), 2, 0.02)
    before = attn.init_params(plain, jax.random.key(0), 2, 0.02)
    assert params["q_norm"].shape == params["k_norm"].shape == (2, 32)
    assert set(params) - set(before) == {"q_norm", "k_norm"}
    assert all(np.array_equal(params[name], before[name]) for name in before)
    assert attn.num_params(config) - attn.num_params(plain) == 2 * 32
    assert set(attn.logical_axes(config)) == set(params)
    whole = dataclasses.replace(config, qk_norm=True)
    assert attn.init_params(whole, jax.random.key(0), 1, 0.02)[
        "k_norm"].shape == (1, 2 * 32)


# -------------------------------------------- (5) the share ties to the model
def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The expert layer's output over the shares [0, 4), [4, 8), [8, 12),
    [12, 16) of the tiny preset's 16 experts (the cell's four chips: 0-7,
    8-15, 16-23, 24-31 of 32) sums to the reference's layer with every
    expert held: no shared expert, nothing counted twice."""
    from ray_tpu.models import experts

    whole = families.float32(FAMILY, experts_held=None)
    blk = jax.tree.map(lambda a: a[0], experts.init_params(
        whole, jax.random.key(0), 1, 0.02))
    blk["router"] = blk["router"] * 20.0
    x = jax.random.normal(jax.random.key(1), (2, 64, whole.d_model))
    ref_cfg = {"experts_held": [0, 16], "num_experts_per_tok": 2,
               "norm_topk_prob": True, "routed_scaling_factor": 1,
               "num_experts_published": 16, "router_bias_seed": 0,
               "router_bias_std": 0.05}

    def part(first, stop):
        config = families.float32(FAMILY, experts_held=range(first, stop))
        held = dict(blk, **{name: blk[name][first:stop]
                            for name in ("w_gate", "w_up", "w_down")})
        layer = experts.layer(config, experts.logical_axes(config), 0)
        return layer(x, held)[0] - x

    with jax.default_matmul_precision("highest"):
        parts = [part(first, first + 4) for first in range(0, 16, 4)]
        h = _rmsnorm(x, blk["mlp_norm"], whole.rms_eps).reshape(128, -1)
        want = reference.experts(h, blk, ref_cfg, 0).reshape(x.shape)
        unbiased = reference.experts(
            h, blk, dict(ref_cfg, router_bias_std=0.0), 0).reshape(x.shape)
    assert rel_err(sum(parts), want) < 1e-4
    assert all(rel_err(p, want) > 0.05 for p in parts)  # no share is all
    assert rel_err(unbiased, want) > 1e-3  # the bias picked some experts


# ----------------------------------------- (6) the kind and the file's cut
def test_the_convolution_kind_counts_itself_and_borrows_its_convolution():
    """The kind's taps and gates are 2 K + 2 FLOPs a channel and no S x S
    product, its parameters the two projections, the taps and the norm; its
    convolution is ``mamba2.causal_conv``."""
    config = families.preset(FAMILY)
    D = config.d_model
    assert shortconv.mixer_flops(config, 128) == 8 * D
    assert shortconv.num_params(config) == 4 * D * D + 3 * D + D
    assert hybrid.KINDS["C"].module is shortconv
    assert shortconv.causal_conv is mamba2.causal_conv


def test_the_rehearsal_file_runs_the_layers_its_cut_names():
    config = spec.load_json(spec.BENCH_DIR, "configs", "tiny-lfm2.json")
    module = spec.load_module("models", FAMILY)
    assert module.layers_run(config) == [0, 2, 3, 4, 5]
    assert module.pattern(config) == "CD*ECE*ECE"
