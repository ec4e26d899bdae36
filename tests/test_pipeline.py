"""Pipeline parallelism (parallel/pipeline.py) + MoE expert parallelism
(models/moe.py) on the virtual 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2, moe
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


def test_gpt2_pipelined_forward_matches_unpipelined():
    mesh = make_mesh(MeshSpec(pipe=2, data=2, tensor=2))
    base = gpt2.GPTConfig(vocab_size=512, n_layer=4, n_head=4, d_model=64,
                          seq_len=32, dtype=jnp.float32, remat=False,
                          attn_impl="xla")
    pp = gpt2.GPTConfig(vocab_size=512, n_layer=4, n_head=4, d_model=64,
                        seq_len=32, dtype=jnp.float32, remat=False,
                        attn_impl="xla", pp_stages=2, pp_microbatches=2)
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
def test_moe_routing_capacity_and_weights():
    config = moe.MoEConfig.tiny()
    x = jax.random.normal(jax.random.key(0), (64, config.d_model))
    w = jax.random.normal(jax.random.key(1),
                          (config.d_model, config.n_experts))
    dispatch, combine, aux = moe._route(x, w, config)
    N, E, C = dispatch.shape
    # No expert over capacity; each token dispatched <= top_k times.
    assert np.all(np.asarray(dispatch.sum(axis=(0, 2))) <= C + 1e-6)
    per_token = np.asarray(dispatch.sum(axis=(1, 2)))
    assert np.all(per_token <= config.top_k + 1e-6)
    # Combine weights of a dispatched token sum to ~1.
    kept = per_token > 0
    csum = np.asarray(combine.sum(axis=(1, 2)))
    np.testing.assert_allclose(csum[kept], 1.0, rtol=1e-5)
    assert float(aux) > 0


def test_moe_forward_and_train_step_expert_parallel():
    mesh = make_mesh(MeshSpec(data=2, expert=4))
    config = moe.MoEConfig(vocab_size=256, n_layer=2, n_head=4, d_model=64,
                           seq_len=32, n_experts=4, expert_mlp=128,
                           dtype=jnp.float32, attn_impl="xla")
    import optax

    opt = optax.adam(1e-2)
    params, opt_state = create_sharded_state(
        lambda k: moe.init_params(config, k), moe.logical_axes(config),
        mesh, jax.random.key(0), opt)
    # Expert weights actually sharded over the expert axis.
    sh = params["blocks"]["expert_in_w"].sharding
    assert "expert" in (sh.spec[1] if isinstance(sh.spec[1], str) else "") \
        or sh.spec[1] == "expert"

    step = jit_train_step(moe.make_train_step(config, opt), mesh=mesh)
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
    """Same params: EP-sharded forward == unsharded forward."""
    config = moe.MoEConfig(vocab_size=128, n_layer=2, n_head=2, d_model=32,
                           seq_len=16, n_experts=4, expert_mlp=64,
                           dtype=jnp.float32, remat=False, attn_impl="xla")
    params = moe.init_params(config, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 128, (4, 16)), jnp.int32)
    ref, aux_ref = moe.forward(params, tokens, config)

    mesh = make_mesh(MeshSpec(expert=4, data=2))
    with jax.set_mesh(mesh):
        sharded = jax.device_put(
            params, pytree_sharding(moe.logical_axes(config), mesh))
        got, aux = jax.jit(lambda p, t: moe.forward(p, t, config))(
            sharded, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-4)
