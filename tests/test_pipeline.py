"""Pipeline parallelism (parallel/pipeline.py) + MoE expert parallelism
(models/moe.py) on the virtual 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2, llama, moe
from ray_tpu.parallel import (MeshSpec, batch_sharding, make_mesh,
                              pipeline_apply, pytree_sharding)
from ray_tpu.parallel.train_state import create_sharded_state, jit_train_step


@pytest.fixture(scope="module")
def pipe_mesh():
    return make_mesh(MeshSpec(pipe=4, data=2))


def test_pipeline_matches_sequential(pipe_mesh):
    """pipeline_apply == sequentially applying all layers."""
    key = jax.random.key(0)
    L, D = 8, 16
    w = jax.random.normal(key, (L, D, D)) * 0.1
    x = jax.random.normal(jax.random.key(1), (8, D))

    def stage_fn(local_w, h):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        h, _ = jax.lax.scan(body, h, local_w)
        return h

    expect = stage_fn(w, x)  # all layers in one scan
    with jax.set_mesh(pipe_mesh):
        got = jax.jit(
            lambda w, x: pipeline_apply(stage_fn, w, x, n_microbatches=4)
        )(w, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_gradients_match(pipe_mesh):
    L, D = 4, 8
    w = jax.random.normal(jax.random.key(0), (L, D, D)) * 0.1
    x = jax.random.normal(jax.random.key(1), (4, D))

    def stage_fn(local_w, h):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        h, _ = jax.lax.scan(body, h, local_w)
        return h

    def seq_loss(w):
        return jnp.sum(stage_fn(w, x) ** 2)

    def pipe_loss(w):
        return jnp.sum(pipeline_apply(stage_fn, w, x, n_microbatches=2) ** 2)

    g_seq = jax.grad(seq_loss)(w)
    with jax.set_mesh(pipe_mesh):
        g_pipe = jax.jit(jax.grad(pipe_loss))(w)
    np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("scan_layers", [True, False])
def test_gpt2_pipelined_forward_matches_unpipelined(scan_layers):
    """A stage runs its slice of the layers as the model runs all of them:
    under lax.scan or, with scan_layers=False, the Python loop."""
    mesh = make_mesh(MeshSpec(pipe=2, data=2, tensor=2))
    base = gpt2.GPTConfig(vocab_size=512, n_layer=4, n_head=4, d_model=64,
                          seq_len=32, dtype=jnp.float32, remat=False,
                          attn_impl="xla")
    pp = gpt2.GPTConfig(vocab_size=512, n_layer=4, n_head=4, d_model=64,
                        seq_len=32, dtype=jnp.float32, remat=False,
                        attn_impl="xla", pp_stages=2, pp_microbatches=2,
                        scan_layers=scan_layers)
    params = gpt2.init_params(base, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 512, (4, 32)), jnp.int32)

    ref = gpt2.forward(params, tokens, base)
    with jax.set_mesh(mesh):
        sharded = jax.device_put(
            params, pytree_sharding(gpt2.logical_axes(pp), mesh))
        got = jax.jit(lambda p, t: gpt2.forward(p, t, pp))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_gpt2_pipelined_train_step():
    """Full dp+pp+tp train step: loss decreases over a few steps."""
    mesh = make_mesh(MeshSpec(pipe=2, data=2, tensor=2))
    config = gpt2.GPTConfig(vocab_size=256, n_layer=4, n_head=4, d_model=64,
                            seq_len=32, dtype=jnp.float32, attn_impl="xla",
                            pp_stages=2, pp_microbatches=2)
    opt = gpt2.make_optimizer(1e-2)
    params, opt_state = create_sharded_state(
        lambda k: gpt2.init_params(config, k), gpt2.logical_axes(config),
        mesh, jax.random.key(0), opt)
    step = jit_train_step(gpt2.make_train_step(config, opt), mesh=mesh)
    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        jnp.asarray(rng.integers(0, 256, (4, 32)), jnp.int32),
        batch_sharding(mesh))
    targets = jnp.roll(tokens, -1, axis=1)
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


# ------------------------------------------------------------------- MoE/EP
def _moe_config(**kw):
    base = dict(vocab_size=256, n_layer=2, n_head=4, n_kv_head=4, d_model=64,
                d_ff=128, seq_len=32, n_experts=4, experts_per_token=2,
                qk_norm=True, router_aux_loss_coef=0.01,
                router_z_loss_coef=0.001, dtype=jnp.float32,
                logits_dtype=jnp.float32, attn_impl="xla")
    base.update(kw)
    return llama.LlamaConfig(**base)


def test_moe_routing_capacity_and_weights():
    """Dropless: there is no capacity.  Group sizes sum to N x k, every
    (token, slot) pair has exactly one row, and the combine weights are the
    chosen probabilities as they stand."""
    N, D, E, K = 64, 128, 4, 2
    x = jax.random.normal(jax.random.key(0), (N, D))
    w = jax.random.normal(jax.random.key(1), (D, E))
    weights, experts, (balance, z) = moe.route(x, w, K, False)
    order, inverse, group_sizes = moe.sort_pairs(experts, E)
    assert int(group_sizes.sum()) == N * K
    np.testing.assert_array_equal(np.sort(np.asarray(order)),
                                  np.arange(N * K))
    np.testing.assert_array_equal(
        np.asarray(order)[np.asarray(inverse).reshape(-1)], np.arange(N * K))
    # rows in expert order, each expert's rows as many as its group size
    sorted_ids = np.asarray(experts).reshape(-1)[np.asarray(order)]
    assert np.all(np.diff(sorted_ids) >= 0)
    np.testing.assert_array_equal(np.bincount(sorted_ids, minlength=E),
                                  np.asarray(group_sizes))
    # a token's k experts differ, and its weights are their probabilities
    assert np.all(np.asarray(experts[:, 0]) != np.asarray(experts[:, 1]))
    probs = np.asarray(jax.nn.softmax(
        jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST), axis=-1))
    np.testing.assert_allclose(
        np.asarray(weights),
        np.take_along_axis(probs, np.asarray(experts), axis=-1), rtol=1e-5)
    renormed, _, _ = moe.route(x, w, K, True)
    np.testing.assert_allclose(np.asarray(renormed.sum(-1)), 1.0, rtol=1e-5)
    assert float(balance) > 0 and float(z) > 0


def test_moe_forward_and_train_step_expert_parallel():
    mesh = make_mesh(MeshSpec(data=2, expert=4))
    config = _moe_config()
    import optax

    opt = optax.adam(1e-2)
    params, opt_state = create_sharded_state(
        lambda k: llama.init_params(config, k), llama.logical_axes(config),
        mesh, jax.random.key(0), opt)
    # Expert weights are stored sharded over the expert axis (the layer
    # gathers them on the way in).
    assert params["blocks"]["w_gate"].sharding.spec[1] == "expert"

    step = jit_train_step(llama.make_train_step(config, opt), mesh=mesh)
    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        jnp.asarray(rng.integers(0, 256, (8, 32)), jnp.int32),
        batch_sharding(mesh))
    targets = jnp.roll(tokens, -1, axis=1)
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_moe_expert_parallel_matches_replicated():
    """Same params: forward and loss with the expert weights stored over the
    expert axis == unsharded."""
    config = _moe_config(vocab_size=128, n_head=2, n_kv_head=2, d_model=32,
                         d_ff=64, seq_len=16, remat=False)
    params = llama.init_params(config, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 128, (4, 16)), jnp.int32)
    ref = llama.forward(params, tokens, config)
    loss_ref = llama.loss_fn(params, tokens, tokens, config)

    mesh = make_mesh(MeshSpec(expert=4, data=2))
    with jax.set_mesh(mesh):
        sharded = jax.device_put(
            params, pytree_sharding(llama.logical_axes(config), mesh))
        got = jax.jit(lambda p, t: llama.forward(p, t, config))(
            sharded, tokens)
        loss = jax.jit(lambda p, t: llama.loss_fn(p, t, t, config))(
            sharded, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-4)
