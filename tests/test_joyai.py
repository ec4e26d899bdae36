"""JD's JoyAI-LLM-Flash through ``models/hybrid.py``: latent attention
(``models/mla.py``: queries and keys from two normed low-rank latents, one
rotary key for all heads, a value head narrower than the q.k head), a leading
dense layer (``models/dense.py``), SwiGLU experts beside a shared expert
under a bias-selected sigmoid router, and the multi-token-prediction module
whose loss joins the step's, for one chip's share of the experts.

The plain reference is ``benchmarks/reference/joyai_llm_flash.py``, the one
copy (float32, the einsum attention, every held expert applied to every
position).  Everything runs on the CPU with seeded random weights at tiny
sizes, attention on the einsum path unless a test says otherwise.  What
every family is held to is ``tests/test_families.py``'s, by the row
``joyai_llm_flash``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import joyai_llm_flash as reference
from benchmarks.reference.llama import _rmsnorm, _rope
from ray_tpu.models import hybrid, mla, moe
from ray_tpu.models.layers import rope
from ray_tpu.ops import attention, remat
from ray_tpu.parallel.train_state import jit_train_step
from ray_tpu.util import first_call, tracing
from tests import families
from tests.families import rel_err

FAMILY = "joyai_llm_flash"


# ----------------------------------------------------- (1) the rotary pass
def _written_out(x, theta, rotary, interleave):
    """The head's last ``rotary`` lanes rotated, by slices."""
    keep, turn = x[..., :x.shape[-1] - rotary], x[..., x.shape[-1] - rotary:]
    turn = reference.rope(turn, theta) if interleave else _rope(turn, theta)
    return jnp.concatenate([keep, turn], axis=-1)


@pytest.mark.parametrize("rotary,interleave", [
    (24, False), (24, True), (8, True), (8, False)],
    ids=["whole-half", "whole-pairs", "part-pairs", "part-half"])
def test_rope_over_a_part_of_a_head_and_in_pairs(rotary, interleave):
    """``layers.rope`` in one pass over heads of 24 lanes: the whole head or
    its last 8 lanes, pairs (i, i + half) or (2i, 2i + 1), forward and
    backward against the sliced formula; the lanes before the rotary part
    pass unchanged."""
    ks = jax.random.split(jax.random.key(rotary + interleave), 2)
    x = jax.random.normal(ks[0], (2, 40, 3, 24))
    do = jax.random.normal(ks[1], x.shape)
    theta = 10000.0
    whole = rotary == x.shape[-1]

    def ours(x):
        return rope(x, theta, None if whole else rotary, interleave)

    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(lambda x: _written_out(x, theta, rotary,
                                                    interleave), x)
        got, ours_pull = jax.vjp(ours, x)
        assert rel_err(got, want) < 1e-5
        assert rel_err(ours_pull(do)[0], pull(do)[0]) < 1e-5
    assert np.array_equal(got[..., :24 - rotary], x[..., :24 - rotary])
    # a rotation: norms of the pairs, so of the head, are kept
    assert np.allclose(jnp.linalg.norm(got, axis=-1),
                       jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_the_two_pairings_differ_by_one_permutation_of_the_lanes():
    """q.k is the same under either pairing once the rotary lanes of both
    are permuted (2i, 2i + 1) -> (i, i + half): what an adapter that kept
    rotate-half would have to state."""
    ks = jax.random.split(jax.random.key(3), 2)
    q, k = (jax.random.normal(key, (1, 32, 2, 16)) for key in ks)
    order = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    pairs = jnp.einsum("bqhd,bkhd->bhqk", rope(q, 1e4, None, True),
                       rope(k, 1e4, None, True))
    halves = jnp.einsum("bqhd,bkhd->bhqk", rope(q[..., order], 1e4),
                        rope(k[..., order], 1e4))
    assert rel_err(pairs, halves) < 1e-5


# ------------------------------------------------------- (2) the ``L`` mixer
def _mixer_parts(config, seed=0, scale=6.0):
    """One mixer's parameters, larger than they start so that the softmax is
    far from uniform and the norms matter, and an input."""
    blk = jax.tree.map(lambda a: a[0] * scale, mla.init_params(
        config, jax.random.key(seed), 1, 0.02))
    for name in ("attn_norm", "q_norm", "kv_norm"):
        blk[name] = 1.0 + 0.1 * jax.random.normal(
            jax.random.key(len(name)), blk[name].shape)
    x = jax.random.normal(jax.random.key(seed + 1), (2, 64, config.d_model))
    return blk, x


def _reference_config(config):
    return {"num_attention_heads": config.mla_heads,
            "qk_nope_head_dim": config.mla_nope_dim,
            "qk_rope_head_dim": config.mla_rope_dim,
            "v_head_dim": config.mla_v_dim, "rms_norm_eps": config.rms_eps,
            "rope_theta": config.mla_rope_theta,
            "kv_lora_rank": config.mla_kv_latent}


def test_mixer_matches_the_written_out_formula():
    """The layer against the einsum formula in float32, its output and the
    gradient of every leaf and of the input: v (16) narrower than q.k (16 +
    8), the rotary pairs interleaved, one rotary key for all four heads."""
    config = families.float32(FAMILY)
    assert (config.mla_nope_dim + config.mla_rope_dim, config.mla_v_dim) \
        == (24, 16)
    blk, x = _mixer_parts(config)
    do = jax.random.normal(jax.random.key(9), x.shape)
    axes = mla.logical_axes(config)

    def ours(blk, x):
        return jnp.sum(mla.mixer(x, blk, config, axes) * do)

    def written_out(blk, x):
        h = _rmsnorm(x, blk["attn_norm"], config.rms_eps)
        return jnp.sum((x + reference.attention(
            h, blk, _reference_config(config), 32)) * do)

    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(ours, (0, 1)))(blk, x)
        want, ref_grads = jax.jit(jax.value_and_grad(written_out, (0, 1)))(
            blk, x)
    assert rel_err(got, want) < 1e-5
    errors = jax.tree.map(rel_err, grads, ref_grads)
    assert set(errors[0]) == {"attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a",
                              "kv_norm", "wkv_b", "wo"}
    for path, err in jax.tree_util.tree_flatten_with_path(errors)[0]:
        assert err < 2e-4, (jax.tree_util.keystr(path), err)


def test_every_head_reads_the_one_rotary_key():
    """``wkv_a``'s last ``mla_rope_dim`` columns make one key a position:
    with q's lanes without position zeroed, every head's scores come from
    that key alone, and zeroing those columns leaves every head a uniform
    causal average of its values."""
    config = families.float32(FAMILY)
    blk, x = _mixer_parts(config)
    H, nope, rot = config.mla_heads, config.mla_nope_dim, config.mla_rope_dim
    wq_b = blk["wq_b"].reshape(-1, H, nope + rot).at[..., :nope].set(0.0)
    blk = dict(blk, wq_b=wq_b.reshape(blk["wq_b"].shape))
    axes = mla.logical_axes(config)
    out = mla.mixer(x, blk, config, axes) - x
    blind = dict(blk, wkv_a=blk["wkv_a"].at[:, config.mla_kv_latent:].set(0))
    flat = mla.mixer(x, blind, config, axes) - x
    h = _rmsnorm(x, blk["attn_norm"], config.rms_eps)
    c_kv = _rmsnorm((h @ blk["wkv_a"])[..., :config.mla_kv_latent],
                    blk["kv_norm"], config.rms_eps)
    v = (c_kv @ blk["wkv_b"]).reshape(2, 64, H, -1)[..., nope:]
    mean = jnp.cumsum(v, axis=1) / jnp.arange(1, 65)[None, :, None, None]
    assert rel_err(flat, mean.reshape(2, 64, -1) @ blk["wo"]) < 1e-4
    assert rel_err(out, flat) > 0.01


@pytest.mark.parametrize("rows", [1, 2])
def test_splash_takes_v_at_its_own_head_dimension(rows):
    """The kernel (interpret mode here) against the einsum path with a q.k
    head of 48 and a v head of 32: output and the three gradients; the scale
    is the q.k head's."""
    S, H = 256, 2
    ks = jax.random.split(jax.random.key(rows), 4)
    q, k = (jax.random.normal(key, (rows, S, H, 48)) for key in ks[:2])
    v = jax.random.normal(ks[2], (rows, S, H, 32))
    do = jax.random.normal(ks[3], v.shape)

    def run(impl):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(attention.causal_attention(
                q, k, v, impl) * do), argnums=(0, 1, 2)))(q, k, v)

    with first_call.noting() as notes:
        (a, ga), (b, gb) = run("xla"), run("splash")
    assert notes["attn_calls"] == 1 and notes["attn_block_q"] == 256
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    for x, y in zip(ga, gb):
        assert float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x)) < 1e-5
    assert attention.causal_attention(q, k, v, "splash").shape == v.shape


# ------------------------------------------------ (3) the prediction module
def test_the_steps_loss_is_the_two_cross_entropies():
    """``loss = loss_main + 0.3 loss_mtp``, the two terms leave as step
    counters, and each is the written-out one: the module's over the last
    layer's output before the final norm joined with the next token's
    embedding (the embedding first), one more ``LE`` block, the shared head,
    the token two ahead, the mean over the S - 1 positions that have one."""
    config = families.float32(FAMILY)
    cfg, _ = families.family(FAMILY, "float32")
    assert cfg["mtp_loss_weight"] == config.mtp_weight == 0.3
    params = hybrid.init_params(config, jax.random.key(0))
    params["experts"]["router"] = params["experts"]["router"] * 20.0
    tokens, targets = families.rows(config.vocab_size)
    ref_cfg = dict(
        _reference_config(config), num_hidden_layers=3,
        first_k_dense_replace=1, num_nextn_predict_layers=1,
        experts_held=[4, 8], num_experts_per_tok=2, norm_topk_prob=True,
        routed_scaling_factor=2.5, n_routed_experts_published=16,
        router_bias_seed=config.router_bias_seed,
        router_bias_std=config.router_bias_std, mtp_loss_weight=0.3)
    with jax.default_matmul_precision("highest"):
        loss, counts = jax.jit(lambda p: hybrid.loss_and_counters(
            p, tokens, targets, config))(params)
        main, ahead = jax.jit(lambda p: reference.losses(
            p, tokens, targets, ref_cfg, 64))(params)
    assert float(loss) == pytest.approx(
        float(counts["loss_main"]) + 0.3 * float(counts["loss_mtp"]),
        rel=1e-6)
    assert float(counts["loss_main"]) == pytest.approx(float(main), rel=1e-5)
    assert float(counts["loss_mtp"]) == pytest.approx(float(ahead), rel=1e-5)
    assert abs(float(main) - float(ahead)) > 1e-4  # two losses, not one twice
    # three expert layers' counters: the pattern's two, then the module's
    assert counts["moe_rows"].shape == (3, 1, 4)
    assert counts["moe_moved"].shape == (3, 1)


def test_the_last_position_weighs_nothing_in_the_modules_loss():
    """A row's last token reaches, causally, only the last position, which
    has no token two ahead: changing it moves ``loss_main`` and leaves
    ``loss_mtp`` where it was; the last target, which the position before
    it predicts two ahead, moves both."""
    config = families.float32(FAMILY)
    params = hybrid.init_params(config, jax.random.key(1))
    tokens, targets = families.rows(config.vocab_size, seed=1)
    losses = jax.jit(lambda t, y: hybrid.loss_and_counters(
        params, t, y, config)[1])
    base = losses(tokens, targets)
    other = tokens.copy()
    other[:, -1] = (other[:, -1] + 7) % config.vocab_size
    moved = losses(other, targets)
    assert float(moved["loss_mtp"]) == pytest.approx(float(base["loss_mtp"]),
                                                     rel=1e-6)
    assert abs(float(moved["loss_main"]) - float(base["loss_main"])) > 1e-5
    later = targets.copy()
    later[:, -1] = (later[:, -1] + 7) % config.vocab_size
    moved = losses(tokens, later)
    assert abs(float(moved["loss_mtp"]) - float(base["loss_mtp"])) > 1e-5
    assert abs(float(moved["loss_main"]) - float(base["loss_main"])) > 1e-5


def test_a_configuration_without_a_module_has_none_of_it():
    config = families.float32(FAMILY, mtp_depth=0)
    params = hybrid.init_params(config, jax.random.key(0))
    assert "mtp" not in params and "mtp" not in hybrid.logical_axes(config)
    assert params["mla"]["wo"].shape[0] == 3
    assert params["experts"]["router"].shape[0] == 2
    tokens, targets = families.rows(config.vocab_size)
    with first_call.noting() as notes:
        _, counts = jax.jit(lambda p: hybrid.loss_and_counters(
            p, tokens, targets, config))(params)
    assert set(counts) == {"moe_rows", "moe_moved"}
    assert "mtp_depth" not in notes
    # the module's rows are drawn after the layers': the layers' are the same
    with_module = hybrid.init_params(families.float32(FAMILY),
                                     jax.random.key(0))
    assert with_module["mla"]["wo"].shape[0] == 4


def test_the_modules_block_is_one_more_of_the_last_layer():
    """No field spells the block: it is the pattern's last two kinds.  Where
    that layer holds no experts the module adds a row to its own kinds'
    stacks and the pattern's expert layers keep their counters."""
    assert families.float32(FAMILY).mtp_kinds == "LE"
    assert families.float32(FAMILY, mtp_depth=0).mtp_kinds == ""
    config = families.float32(FAMILY, pattern="LELD")
    assert config.mtp_kinds == "LD"
    assert [config.rows(kind) for kind in "LED"] == [3, 1, 2]
    params = hybrid.init_params(config, jax.random.key(0))
    assert params["dense"]["w_down"].shape[0] == 2
    assert params["experts"]["router"].shape[0] == 1
    tokens, targets = families.rows(config.vocab_size)
    loss, counts = jax.jit(lambda p: hybrid.loss_and_counters(
        p, tokens, targets, config))(params)
    assert set(counts) == {"moe_rows", "moe_moved", "loss_main", "loss_mtp"}
    assert counts["moe_rows"].shape == (1, 1, 4)
    assert float(loss) == pytest.approx(
        float(counts["loss_main"]) + 0.3 * float(counts["loss_mtp"]),
        rel=1e-6)


# ------------------------------------------------------ (4) the whole model
def test_the_pattern_trains_through_make_train_step():
    """``LDLELE`` with the module through ``make_train_step`` under
    ``jit_train_step``: the loss falls, and the step's counters hold the two
    losses beside the three expert layers' rows."""
    config = families.preset(FAMILY, attn_impl="xla")
    optimizer = hybrid.make_optimizer(learning_rate=3e-3)
    params = hybrid.init_params(config, jax.random.key(0))
    opt_state = optimizer.init(params)
    raw = hybrid.make_train_step(config, optimizer)
    step = jit_train_step(raw)
    tokens, targets = families.rows(config.vocab_size, seed=2)
    losses = []
    for _ in range(4):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    counters = {name: np.asarray(ref[...])
                for name, ref in raw.counters.items()}
    assert set(counters) == {"moe_rows", "moe_moved", "loss_main", "loss_mtp"}
    assert counters["moe_rows"].shape == (3, 1, 4)
    assert counters["loss_main"].shape == () \
        and counters["loss_main"].dtype == np.float32
    assert losses[-1] == pytest.approx(
        float(counters["loss_main"]) + 0.3 * float(counters["loss_mtp"]),
        rel=1e-3)


def test_the_remat_rule_is_given_the_modules_sizes():
    """``_layer_sizes`` counts the module's two layers among the kept
    inputs and the candidates, and a third set of logits at the head."""
    config = families.preset(FAMILY, attn_impl="xla")
    plain = dataclasses.replace(config, mtp_depth=0)

    def sizes(config):
        shapes = jax.eval_shape(lambda: hybrid.init_params(
            config, jax.random.key(0)))
        return hybrid._layer_sizes(shapes, (2, 128, config.d_model), config)

    (with_module, _), (without, _) = sizes(config), sizes(plain)
    tokens = 256
    qkv = tokens * 2 * (2 * 24 + 16) * 2  # two heads: q and k of 24, v of 16
    shared = tokens * 2 * 48 * 2
    dense = tokens * 2 * 128 * 2
    routing = moe.routing_bytes(tokens, 16, 2)
    latents = tokens * (48 + 32 + 8) * 2  # the two latents, the rotary key
    assert families.named(without) == {remat.GATE_UP: 2 * shared + dense,
                                     remat.LATENTS: 3 * latents,
                                     remat.QKV: 3 * qkv,
                                     remat.ROUTING: 2 * routing}
    assert families.named(with_module) == {remat.GATE_UP: 3 * shared + dense,
                                         remat.LATENTS: 4 * latents,
                                         remat.QKV: 4 * qkv,
                                         remat.ROUTING: 3 * routing}
    # the module's layers are rows of their kinds' groups
    assert {(r.group, r.name): r.layers for r in with_module} == {
        ("E", remat.GATE_UP): 3, ("E", remat.ROUTING): 3,
        ("L", remat.LATENTS): 4, ("L", remat.QKV): 4,
        ("D", remat.GATE_UP): 1}


def test_the_registries_hold_the_new_scopes_and_counters():
    """(That the lowered step holds them: ``tests/test_step_names.py``,
    ``hybrid-mla``.)"""
    assert {"latent", "mtp", "mtp_head"} <= set(tracing.SCOPE_REGISTRY)
    assert {"loss_main", "loss_mtp"} <= set(tracing.STEP_COUNTER_REGISTRY)
