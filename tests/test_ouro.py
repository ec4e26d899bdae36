"""What only the looped family has (Ouro-2.6B; ``models/looped.py`` under
``models/llama.py``): the stack run ``ut_steps`` times over with one set of
weights against the same layers written out ``T x L`` deep with tied copies,
the exit distribution, the entropy term's reach, the two step counters, what
the remat rule is told, the head's per-position form, and that the plain
decoder traces none of it.  What every family is held to is
``tests/test_families.py``'s, the row ``ouro`` of ``tests/families.py``.

CPU, seeded weights, the rehearsal file's sizes, attention on the einsum
path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, looped
from ray_tpu.models.layers import rmsnorm
from ray_tpu.ops import remat
from ray_tpu.ops.lm_head import (lm_head_cross_entropy,
                                 lm_head_cross_entropy_by_position)
from ray_tpu.parallel.train_state import jit_train_step
from ray_tpu.train import StepProfiler
from ray_tpu.train import profiler as train_profiler
from tests import families

T, L = 4, 2


@pytest.fixture(scope="module")
def config():
    config = families.float32("ouro")
    assert (config.ut_steps, config.n_layer, config.sandwich_norm) \
        == (T, L, True)
    return config


@pytest.fixture(scope="module")
def params():
    return families.shaken("ouro", families.drawn("ouro"))


@pytest.fixture(scope="module")
def ids(config):
    return families.rows(config.vocab_size)


def _unrolled_loss(deep, params, tokens, targets, config):
    """The looped loss with the passes written out: ``deep`` holds the
    blocks ``T x L`` deep, a layer application each, and nothing loops."""
    axes = llama.logical_axes(config)["blocks"]
    x = params["wte"][tokens].astype(config.dtype)
    head = params["lm_head"].astype(config.dtype)
    ce, z = [], []
    for t in range(T):
        for layer in range(L):
            blk = jax.tree.map(lambda a: a[t * L + layer], deep)
            x = llama.feed_forward(llama.attention(x, blk, config, axes),
                                   blk, config, axes)[0]
        x = rmsnorm(x, params["final_norm"], config.rms_eps).astype(
            config.dtype)
        logits = x @ head.T
        ce.append(jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0])
        z.append(x @ params["exit_gate"][:-1] + params["exit_gate"][-1])
    return looped.expected_loss(
        jnp.stack(ce), jax.nn.sigmoid(jnp.stack(z[:-1])), config.exit_beta)[0]


def test_the_loop_is_the_layers_written_out_with_tied_copies(config, params,
                                                             ids):
    """Equal loss, and a block's gradient is the sum of its ``T`` copies'."""
    tokens, targets = ids
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, targets, config)))(params)
    deep = jax.tree.map(lambda a: jnp.tile(a, (T,) + (1,) * (a.ndim - 1)),
                        params["blocks"])
    want, (deep_grads, rest) = jax.jit(jax.value_and_grad(
        lambda d, p: _unrolled_loss(d, p, tokens, targets, config),
        argnums=(0, 1)))(deep, params)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    for name, got in grads["blocks"].items():
        copies = deep_grads[name].reshape(T, L, *got.shape[1:])
        assert families.rel_err(got, copies.sum(0)) < 2e-4, name
        # no copy's share is negligible: every pass trains the block
        assert all(float(jnp.abs(c).max()) > 0 for c in copies), name
    for name in ("wte", "lm_head", "final_norm", "exit_gate"):
        assert families.rel_err(grads[name], rest[name]) < 2e-4, name


def test_the_exit_distribution_sums_to_one():
    lam = jax.random.uniform(jax.random.key(0), (T - 1, 3, 5))
    q = looped.exit_distribution(lam)
    assert q.shape == (T, 3, 5) and float(q.min()) > 0
    np.testing.assert_allclose(np.asarray(q.sum(0)), 1.0, rtol=1e-6)
    # what a gate at zero gives: a half leaves at each pass, the last
    # takes what is left
    halves = looped.exit_distribution(jnp.full((T - 1, 1), 0.5))
    np.testing.assert_array_equal(np.asarray(halves[:, 0]),
                                  [1 / 2, 1 / 4, 1 / 8, 1 / 8])


def test_a_fresh_gate_starts_at_a_half(config, ids):
    """The drawn gate is zero, weight and bias: the first step's exit mass
    is (1/2, 1/4, 1/8, 1/8) on every position, so every head trains."""
    tokens, targets = ids
    fresh = families.drawn("ouro")
    assert not np.asarray(fresh["exit_gate"]).any()
    _, counters = jax.jit(lambda p: llama.loss_and_counters(
        p, tokens, targets, config))(fresh)
    np.testing.assert_allclose(np.asarray(counters["ut_exit_mass"]),
                               [1 / 2, 1 / 4, 1 / 8, 1 / 8], rtol=1e-6)


def test_the_entropy_term_reaches_only_the_gate_and_what_feeds_it(
        config, params, ids):
    """``beta`` moves the gradient of the gate and of everything the gate's
    input passes through, and leaves the head's alone: no logit is a
    function of ``q``."""
    tokens, targets = ids

    def grads(beta):
        c = dataclasses.replace(config, exit_beta=beta)
        return jax.jit(jax.grad(
            lambda p: llama.loss_fn(p, tokens, targets, c)))(params)

    with_entropy, without = grads(0.1), grads(0.0)
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         with_entropy, without)
    assert moved["lm_head"] == 0.0
    assert moved["exit_gate"] > 0 and moved["final_norm"] > 0 \
        and moved["wte"] > 0
    assert all(m > 0 for m in moved["blocks"].values())


def test_the_counters_leave_the_step(config, ids):
    """``loss_ut`` and ``ut_exit_mass`` come out of the compiled step
    through its refs and onto the profiler's row; the exit mass sums to one
    and the loss is their expectation less the entropy term."""
    tokens, _ = ids
    optimizer = llama.make_optimizer()
    params = families.drawn("ouro")
    opt_state = jax.jit(optimizer.init)(params)
    profiler = StepProfiler(run_name="ouro", rank=0)
    train_profiler.activate(profiler)
    try:
        step = jit_train_step(llama.make_train_step(config, optimizer),
                              donate_state=False)
        assert set(step._counters) == set()  # made when first traced
        _, _, loss = step(params, opt_state, tokens, tokens)
        profiler.step_boundary()
        (row,) = profiler.history
    finally:
        train_profiler.activate(None)
    assert set(step._counters) == {"loss_ut", "ut_exit_mass"}
    loss_ut, mass = (np.asarray(row[name])
                     for name in ("loss_ut", "ut_exit_mass"))
    assert loss_ut.shape == mass.shape == (T,)
    assert all(ref.dtype == jnp.float32 for ref in step._counters.values())
    np.testing.assert_allclose(mass.sum(), 1.0, rtol=1e-5)
    assert np.all(loss_ut > 0)
    # sum_t mean(q_t) mean(CE_t) is the expectation up to the covariance of
    # q and CE over positions, small at a fresh gate; the entropy term is at
    # most beta ln T
    assert abs(float(loss) - float((mass * loss_ut).sum())) \
        < 0.1 * np.log(T) + 0.05


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_remat_rule_counts_what_the_scans_stack_a_pass(passes, config):
    """The ladder's candidates grow by ``ut_steps`` (every pass stacks its
    own q, k, v and MLP products), and so does what ``own_temporaries``
    takes the scans to stack; the head's term does not: the head holds one
    pass's logits."""
    def sizes(passes):
        c = dataclasses.replace(config, ut_steps=passes)
        shapes = jax.eval_shape(lambda: llama.init_params(
            c, jax.random.key(0)))
        return llama._layer_sizes(shapes, (2, c.seq_len, c.d_model), c)

    (rungs, bound), (once, plain) = sizes(passes), sizes(1)
    assert [name for name, _ in rungs] == list(remat.LADDER)
    assert [size for _, size in rungs] == [passes * size for _, size in once]
    assert (bound > plain) == (passes > 1)

    def temporaries(**sizes):
        return remat.own_temporaries(**{**dict(
            block_bytes=0, other_bytes=0, layer_bytes=0, sharded=False,
            tokens=256, d_model=64, n_layer=2, attn_width=64, n_head=2,
            mlp_width=128, vocab=512, itemsize=2, logits_itemsize=2),
            **sizes})

    stack = 2 * 256 * (64 * 2 + 64 * 2 + 2 * 4)  # two layers', a pass
    working = 6 * 256 * (128 + 64) * 2
    assert temporaries() == stack + working
    # a pass more stacks a pass's more, once sliced out for the layers'
    # scan, and keeps its state four times over; the logits stay one pass's
    state = 256 * 64 * (2 * 2 + 2 * 4)
    assert temporaries(passes=passes) == stack + working + (
        (passes - 1) * stack + stack + passes * state if passes > 1 else 0)
    assert temporaries(vocab=1 << 16, passes=passes) \
        == passes * stack + 2 * 256 * (1 << 16) * 2


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_heads_per_position_form_is_the_mean_forms(dtype):
    """Values, and the gradients of any weighting of the positions, against
    ``lm_head_cross_entropy``; under a mean it is the mean form."""
    dt = jnp.dtype(dtype)
    kx, kh, kt, kw = jax.random.split(jax.random.key(2), 4)
    x = jax.random.normal(kx, (2, 16, 32)).astype(dt)
    head = (jax.random.normal(kh, (64, 32)) * 0.3).astype(dt)
    targets = jax.random.randint(kt, (2, 16), 0, 64)
    weights = jax.random.uniform(kw, (2, 16))

    def by_position(x, head, weights):
        return jnp.sum(lm_head_cross_entropy_by_position(
            x, head, targets, dt) * weights)

    def summed(x, head, weights):
        return lm_head_cross_entropy(x, head, targets, dt, weights)

    got, got_grads = jax.value_and_grad(by_position, argnums=(0, 1, 2))(
        x, head, weights)
    want, want_grads = jax.value_and_grad(summed, argnums=(0, 1, 2))(
        x, head, weights)
    tol = 1e-5 if dtype == "float32" else 2e-2
    assert abs(float(got) - float(want)) <= tol * abs(float(want))
    for a, b in zip(got_grads, want_grads):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert families.rel_err(a, b) < tol
    mean = jnp.mean(lm_head_cross_entropy_by_position(x, head, targets, dt))
    assert abs(float(mean) - float(lm_head_cross_entropy(
        x, head, targets, dt))) <= tol * float(mean)


def test_the_backward_keeps_no_logits():
    """What the per-position form saves for its backward is (B, S) and
    (B, S, D) wide, nothing (B, S, V): the logits are made again."""
    from jax._src.ad_checkpoint import saved_residuals

    x, head = jnp.ones((2, 16, 32)), jnp.ones((64, 32))
    targets = jnp.zeros((2, 16), jnp.int32)
    kept = saved_residuals(
        lambda x, head: lm_head_cross_entropy_by_position(
            x, head, targets, jnp.float32).sum(), x, head)
    assert all(64 not in aval.shape[2:] for aval, _ in kept), kept
    mean_form = saved_residuals(
        lambda x, head: lm_head_cross_entropy(x, head, targets, jnp.float32),
        x, head)
    assert any(aval.shape == (2, 16, 64) for aval, _ in mean_form)


def test_forward_gives_the_last_passes_logits(config, params, ids):
    tokens, _ = ids
    logits = jax.jit(lambda p: llama.forward(p, tokens, config))(params)
    states = jax.jit(lambda p: llama.forward_hidden(p, tokens, config)[0])(
        params)
    assert states.shape == (T, *tokens.shape, config.d_model)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(states[-1] @ params["lm_head"].T),
        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("clash", [dict(n_experts=8, experts_per_token=2),
                                   dict(block_length=4), dict(ut_steps=0)])
def test_a_loop_over_experts_or_blocks_is_refused(clash):
    with pytest.raises(ValueError, match="ut_steps"):
        llama.LlamaConfig(**{"ut_steps": 4, **clash})


def test_the_plain_decoder_has_no_gate_and_no_second_norms():
    """At the defaults no parameter, axis or counter of the loop exists and
    the counts are the plain decoder's (its lowered step is pinned in
    ``tests/data/lowered_steps.json``)."""
    plain = llama.LlamaConfig.tiny()
    shapes = jax.eval_shape(lambda: llama.init_params(plain,
                                                      jax.random.key(0)))
    assert "exit_gate" not in shapes and "exit_gate" not in \
        llama.logical_axes(plain)
    assert not {"attn_norm_2", "mlp_norm_2"} & set(shapes["blocks"])
    looped_twin = dataclasses.replace(plain, ut_steps=T, sandwich_norm=True)
    extra = llama.num_params(looped_twin) - llama.num_params(plain)
    assert extra == 2 * plain.n_layer * plain.d_model + plain.d_model + 1
    # the looped twin's residual projections start smaller: 2 L T branches
    wo, wo_plain = (llama.init_params(c, jax.random.key(0))["blocks"]["wo"]
                    for c in (looped_twin, plain))
    np.testing.assert_allclose(np.asarray(wo) * np.sqrt(T),
                               np.asarray(wo_plain), rtol=1e-6)
