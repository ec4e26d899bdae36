"""Llama-family model tests: shapes, GQA equivalence, training convergence,
sharded multi-device step (same contract as tests/test_models.py for GPT-2).
"""

import contextlib
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama


def test_forward_shapes_and_param_count():
    config = llama.LlamaConfig.tiny()
    params = llama.init_params(config, jax.random.key(0))
    counted = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert counted == llama.num_params(config)

    tokens = jnp.zeros((2, config.seq_len), jnp.int32)
    logits = jax.jit(lambda p, t: llama.forward(p, t, config))(params, tokens)
    assert logits.shape == (2, config.seq_len, config.vocab_size)
    assert jnp.isfinite(logits).all()


def test_gqa_equivalent_to_mha_with_tiled_kv():
    """GQA with kv projections TILED to full heads must equal MHA exactly:
    the repeat path shares each kv head across its query group, so an MHA
    model whose wk/wv duplicate the kv heads per group is the same function.
    """
    gqa = llama.LlamaConfig(vocab_size=256, n_layer=1, n_head=4, n_kv_head=2,
                            d_model=64, d_ff=128, seq_len=32,
                            dtype=jnp.float32, attn_impl="xla")
    mha = llama.LlamaConfig(vocab_size=256, n_layer=1, n_head=4, n_kv_head=4,
                            d_model=64, d_ff=128, seq_len=32,
                            dtype=jnp.float32, attn_impl="xla")
    params = llama.init_params(gqa, jax.random.key(1))
    hd, D = gqa.head_dim, gqa.d_model

    def tile_kv(w):
        # (L, D, KV*hd) -> (L, D, KV, hd) -> repeat each kv head q_per_kv
        # times along the head axis -> (L, D, H*hd).
        L = w.shape[0]
        heads = w.reshape(L, D, gqa.n_kv_head, hd)
        return jnp.repeat(heads, gqa.q_per_kv, axis=2).reshape(L, D, -1)

    params_mha = dict(params)
    params_mha["blocks"] = dict(params["blocks"])
    params_mha["blocks"]["wk"] = tile_kv(params["blocks"]["wk"])
    params_mha["blocks"]["wv"] = tile_kv(params["blocks"]["wv"])

    tokens = jax.random.randint(jax.random.key(2), (2, 32), 0, 256)
    out_gqa = llama.forward(params, tokens, gqa)
    out_mha = llama.forward(params_mha, tokens, mha)
    np.testing.assert_allclose(np.asarray(out_gqa), np.asarray(out_mha),
                               rtol=1e-5, atol=1e-5)


def test_rope_is_position_sensitive():
    x = jnp.ones((1, 8, 2, 16))
    rotated = llama._rope(x, 10000.0)
    # Identical inputs at different positions must rotate differently.
    assert not jnp.allclose(rotated[0, 0], rotated[0, 5])
    # Position 0 rotates by angle 0: unchanged.
    np.testing.assert_allclose(rotated[0, 0], x[0, 0], rtol=1e-6)


def test_tiny_training_step_reduces_loss():
    config = llama.LlamaConfig.tiny()
    opt = llama.make_optimizer(learning_rate=1e-2)
    params = llama.init_params(config, jax.random.key(0))
    opt_state = opt.init(params)
    step = jax.jit(llama.make_train_step(config, opt))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, config.vocab_size, (4, config.seq_len + 1)),
                       jnp.int32)
    tokens, targets = toks[:, :-1], toks[:, 1:]
    losses = []
    for _ in range(15):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5, losses[::5]
    assert np.isfinite(losses).all()


def test_sharded_train_step_dp_fsdp_tp():
    """Full sharded step over the 8-device CPU mesh — the llama stack rides
    the same logical-axis rules as GPT-2."""
    from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh
    from ray_tpu.parallel.train_state import (create_sharded_state,
                                              jit_train_step)

    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    spec = MeshSpec(data=2, fsdp=2, tensor=2)
    mesh = make_mesh(spec, devices[:8])
    config = llama.LlamaConfig.tiny()
    opt = llama.make_optimizer(learning_rate=1e-3)
    params, opt_state = create_sharded_state(
        lambda k: llama.init_params(config, k), llama.logical_axes(config),
        mesh, jax.random.key(0), opt)
    step = jit_train_step(llama.make_train_step(config, opt), mesh=mesh)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, config.vocab_size, (8, config.seq_len + 1)),
                       jnp.int32)
    tokens = jax.device_put(toks[:, :-1], batch_sharding(mesh))
    targets = jax.device_put(toks[:, 1:], batch_sharding(mesh))
    _, _, loss = step(params, opt_state, tokens, targets)
    assert np.isfinite(float(loss))


# ------------------------------------- what the layer's checkpoint keeps
def _splash_config():
    # interpret-mode splash on the CPU; 128 is the kernel's smallest block
    return dataclasses.replace(llama.LlamaConfig.tiny(), attn_impl="splash",
                               seq_len=128)


def _batch(config):
    toks = jax.random.randint(jax.random.key(1), (2, config.seq_len + 1),
                              0, config.vocab_size)
    return toks[:, :-1], toks[:, 1:]


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its parameters
    (scan, remat, shard_map, custom_vjp, pjit)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _grad_jaxpr(config):
    params = jax.eval_shape(lambda k: llama.init_params(config, k),
                            jax.random.key(0))
    tokens, targets = _batch(config)
    return jax.make_jaxpr(jax.grad(
        lambda p: llama.loss_fn(p, tokens, targets, config)))(params).jaxpr


def _kernel_names(jaxpr):
    return sorted(eqn.params["name"] for eqn in _equations(jaxpr)
                  if eqn.primitive.name == "pallas_call")


def _stacked_by_scans(jaxpr):
    return [len(eqn.outvars) - eqn.params["num_carry"]
            for eqn in _equations(jaxpr) if eqn.primitive.name == "scan"]


@pytest.mark.parametrize("mesh_axes", [None, dict(fsdp=2, tensor=2)],
                         ids=["one_device", "fsdp2_x_tensor2"])
def test_backward_runs_the_splash_forward_once(mesh_axes, monkeypatch):
    """The differentiated step holds one forward and one backward kernel:
    the layer's checkpoint keeps the forward's output and log-sum-exp, also
    where the kernel sits inside ``splash_attention``'s shard_map."""
    from ray_tpu.parallel import MeshSpec, make_mesh

    config = _splash_config()
    mesh = contextlib.nullcontext() if mesh_axes is None else jax.set_mesh(
        make_mesh(MeshSpec(**mesh_axes), jax.devices()[:4]))
    with mesh:
        kept = _grad_jaxpr(config)
        # a bare jax.checkpoint(layer), the layer as it was: two forwards
        monkeypatch.setattr(llama, "save_splash_residuals", None)
        bare = _grad_jaxpr(config)
    assert ("shard_map" in {e.primitive.name for e in _equations(kept)}) \
        == (mesh_axes is not None)
    assert _kernel_names(kept) == ["splash_mha_dkv_no_residuals",
                                   "splash_mha_fwd_residuals"]
    assert _kernel_names(bare) == ["splash_mha_dkv_no_residuals",
                                   "splash_mha_fwd_residuals",
                                   "splash_mha_fwd_residuals"]
    # what the forward scan stacks for each layer: x, and now the output
    # (B, H, S, hd) and the log-sum-exp (B, H, S) of one lane, not the
    # kernel's own 128-lane output (shard_map lays them out per device)
    forward = next(e for e in _equations(kept) if e.primitive.name == "scan")
    B, H, S, hd = 2, config.n_head, config.seq_len, config.head_dim
    assert sorted(
        (str(v.aval.dtype), math.prod(v.aval.shape[1:]))
        for v in forward.outvars[forward.params["num_carry"]:]) == sorted(
        [("bfloat16", B * S * config.d_model), ("bfloat16", B * H * S * hd),
         ("float32", B * H * S)])


def test_saved_splash_residuals_change_no_number(monkeypatch):
    """Loss and every gradient leaf equal those of a bare
    ``jax.checkpoint(layer)``: the backward kernel gets the arrays the
    forward made instead of a second copy from the same kernel."""
    config = _splash_config()
    params = llama.init_params(config, jax.random.key(0))
    batch = _batch(config)

    def run():
        return jax.jit(jax.value_and_grad(llama.loss_fn), static_argnums=3)(
            params, *batch, config)

    loss, grads = run()
    monkeypatch.setattr(llama, "save_splash_residuals", None)
    bare_loss, bare_grads = run()
    assert float(loss) == float(bare_loss) and np.isfinite(float(loss))
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(bare_grads)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_policy_saves_nothing_without_the_kernel(monkeypatch):
    """With the XLA attention path there is no such name in the layer: the
    scans stack what they stacked under a bare checkpoint."""
    config = dataclasses.replace(llama.LlamaConfig.tiny(), attn_impl="xla")
    kept = _grad_jaxpr(config)
    monkeypatch.setattr(llama, "save_splash_residuals", None)
    bare = _grad_jaxpr(config)
    assert _kernel_names(kept) == []
    assert _stacked_by_scans(kept) == _stacked_by_scans(bare)
    assert _stacked_by_scans(kept)[0] == 1  # x alone
