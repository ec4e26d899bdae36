"""Llama-family model tests: shapes, GQA equivalence, training convergence,
sharded multi-device step (same contract as tests/test_models.py for GPT-2).
"""

import contextlib
import dataclasses
import math
import re

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


@pytest.mark.parametrize("impl, heads, seq", [("xla", (4, 2), 32),
                                              ("splash", (8, 2), 128)])
def test_gqa_equivalent_to_mha_with_tiled_kv(impl, heads, seq):
    """GQA must equal an MHA model whose wk/wv duplicate each kv head over its
    query group, loss and every gradient leaf: on the XLA path, where the
    dispatcher repeats k and v, and on the splash path (interpret mode here;
    128 is the kernel's smallest block), where the kernel gets them at KV
    heads and nothing repeats them."""
    H, KV = heads
    gqa = llama.LlamaConfig(vocab_size=256, n_layer=1, n_head=H, n_kv_head=KV,
                            d_model=16 * H, d_ff=128, seq_len=seq,
                            dtype=jnp.float32, logits_dtype=jnp.float32,
                            attn_impl=impl)
    mha = dataclasses.replace(gqa, n_kv_head=H)
    params = llama.init_params(gqa, jax.random.key(1))
    hd, D = gqa.head_dim, gqa.d_model

    def tile_kv(w):
        # (L, D, KV*hd) -> (L, D, KV, hd) -> repeat each kv head q_per_kv
        # times along the head axis -> (L, D, H*hd).
        L = w.shape[0]
        heads = w.reshape(L, D, KV, hd)
        return jnp.repeat(heads, gqa.q_per_kv, axis=2).reshape(L, D, -1)

    def untile_kv(g):
        # the tiled model's gradient, summed back over each group's copies
        L = g.shape[0]
        return g.reshape(L, D, KV, gqa.q_per_kv, hd).sum(3).reshape(L, D, -1)

    params_mha = dict(params)
    params_mha["blocks"] = dict(params["blocks"])
    params_mha["blocks"]["wk"] = tile_kv(params["blocks"]["wk"])
    params_mha["blocks"]["wv"] = tile_kv(params["blocks"]["wv"])

    toks = jax.random.randint(jax.random.key(2), (2, seq + 1), 0, 256)
    tokens, targets = toks[:, :-1], toks[:, 1:]
    out_gqa = llama.forward(params, tokens, gqa)
    out_mha = llama.forward(params_mha, tokens, mha)
    np.testing.assert_allclose(np.asarray(out_gqa), np.asarray(out_mha),
                               rtol=1e-5, atol=1e-5)

    loss_gqa, grads_gqa = jax.value_and_grad(llama.loss_fn)(
        params, tokens, targets, gqa)
    loss_mha, grads_mha = jax.value_and_grad(llama.loss_fn)(
        params_mha, tokens, targets, mha)
    assert float(loss_gqa) == pytest.approx(float(loss_mha), rel=1e-6)
    grads_mha["blocks"]["wk"] = untile_kv(grads_mha["blocks"]["wk"])
    grads_mha["blocks"]["wv"] = untile_kv(grads_mha["blocks"]["wv"])
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grads_gqa),
            jax.tree.leaves(grads_mha)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=0,
            atol=1e-5 * float(jnp.max(jnp.abs(want))),
            err_msg=jax.tree_util.keystr(path))


def test_rope_is_position_sensitive():
    x = jnp.ones((1, 8, 2, 16))
    rotated = llama._rope(x, 10000.0)
    # Identical inputs at different positions must rotate differently.
    assert not jnp.allclose(rotated[0, 0], rotated[0, 5])
    # Position 0 rotates by angle 0: unchanged.
    np.testing.assert_allclose(rotated[0, 0], x[0, 0], rtol=1e-6)


def _sliced_rope(x, theta):
    """The formula as published and as ``_rope`` computed it before it became
    one pass: float32 halves, sliced, rotated, concatenated, rounded once."""
    S, half = x.shape[1], x.shape[3] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


@pytest.mark.parametrize("hd", [16, 64, 128])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_rope_equals_the_sliced_float32_formula(dtype, hd):
    """Values and gradients (the custom backward: the same pass, sine negated)
    equal the sliced formula's and autodiff's through it bit for bit: the
    swap is exact and the float32 products, sum and single rounding are the
    same.  The benchmark's plain reference agrees to float32 rounding."""
    from benchmarks.reference import llama as reference

    theta = 10000.0
    x = jax.random.normal(jax.random.key(hd), (2, 48, 3, hd)).astype(dtype)
    weight = jax.random.normal(jax.random.key(1), x.shape)

    def weighted(rope):
        return lambda x: jnp.sum(rope(x, theta).astype(jnp.float32) * weight)

    got, want = llama._rope(x, theta), _sliced_rope(x, theta)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    got_grad = jax.grad(weighted(llama._rope))(x)
    assert got_grad.dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(got_grad, np.float32),
        np.asarray(jax.grad(weighted(_sliced_rope))(x), np.float32))

    x32 = x.astype(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(llama._rope(x32, theta)),
        np.asarray(reference._rope(x32, theta)), rtol=0, atol=1e-5)


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
        monkeypatch.setattr(llama, "_layer_policy", lambda *a: None)
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
    monkeypatch.setattr(llama, "_layer_policy", lambda *a: None)
    bare_loss, bare_grads = run()
    assert float(loss) == float(bare_loss) and np.isfinite(float(loss))
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(bare_grads)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_policy_saves_nothing_without_the_kernel(monkeypatch):
    """With the XLA attention path there is no such name in the layer: the
    scans stack what they stacked under a bare checkpoint."""
    config = dataclasses.replace(llama.LlamaConfig.tiny(), attn_impl="xla")
    kept = _grad_jaxpr(config)
    monkeypatch.setattr(llama, "_layer_policy", lambda *a: None)
    bare = _grad_jaxpr(config)
    assert _kernel_names(kept) == []
    assert _stacked_by_scans(kept) == _stacked_by_scans(bare)
    assert _stacked_by_scans(kept)[0] == 1  # x alone


# ------------------------------ how q, k and v reach the attention kernel
def _kv_repeats(jaxpr, config):
    """The ``broadcast_in_dim`` equations that copy (.., KV, hd) out to
    (.., KV, H // KV, hd): what ``jnp.repeat`` over the head axis traces to."""
    want = (config.n_kv_head, config.q_per_kv, config.head_dim)
    return [eqn for eqn in _equations(jaxpr)
            if eqn.primitive.name == "broadcast_in_dim"
            and eqn.outvars[0].aval.shape[-3:] == want]


def test_only_the_paths_that_need_equal_heads_repeat_k_and_v():
    """The splash kernel takes k and v at KV heads, so the layer's jaxpr has
    no KV-to-H broadcast, forward or backward; the XLA path still repeats
    (k and v, once each: the backward's remat is a separate jaxpr that
    repeats them again)."""
    config = _splash_config()
    assert config.n_kv_head < config.n_head
    assert _kv_repeats(_grad_jaxpr(config), config) == []
    xla = dataclasses.replace(config, attn_impl="xla")
    assert len(_kv_repeats(_grad_jaxpr(xla), xla)) >= 2


def test_splash_refuses_a_tensor_axis_that_splits_a_kv_group():
    """Under a mesh the heads are divided over `tensor`: it must divide the
    K/V heads as it must divide the query heads, and the error says so."""
    from ray_tpu.parallel import MeshSpec, make_mesh

    config = _splash_config()  # 4 query heads, 2 K/V heads
    with jax.set_mesh(make_mesh(MeshSpec(tensor=4), jax.devices()[:4])):
        with pytest.raises(ValueError, match="tensor axis.*2 K/V heads"):
            _grad_jaxpr(config)


def test_gqa_step_lowers_for_the_tpu_with_k_and_v_at_their_own_heads(
        monkeypatch):
    """Lowering for the TPU needs no TPU.  A two-layer GQA step at head 128:
    what the byte count of PERF.md (PR 27) rests on, read off the text.  The
    two kernels (forward, fused backward) take q at H heads and k, v at KV
    heads and give dk, dv back at KV heads; and nothing between the
    projections and the kernel is a float32 array half a head wide (the
    sliced RoPE's halves, 64 lanes padded to 128 on the chip)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, S, H, KV, hd = 2, 512, 8, 2, 128
    config = llama.LlamaConfig(vocab_size=1024, n_layer=2, n_head=H,
                               n_kv_head=KV, d_model=H * hd, d_ff=1536,
                               seq_len=S)
    optimizer = llama.make_optimizer()
    params = jax.eval_shape(lambda k: llama.init_params(config, k),
                            jax.random.key(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    batch = jax.ShapeDtypeStruct((B, S), jnp.int32)
    text = jax.jit(llama.make_train_step(config, optimizer)).trace(
        params, opt_state, batch, batch).lower(
            lowering_platforms=("tpu",)).as_text()

    q, kv = f"tensor<{B}x{H}x{S}x{hd}xbf16>", f"tensor<{B}x{KV}x{S}x{hd}xbf16>"
    # the splash calls; the rotary pass's are tests/test_rope_kernel.py's
    calls = [line[line.rindex(" : ("):] for line in text.splitlines()
             if "@tpu_custom_call" in line and "rope_" not in line]
    assert len(calls) == 2
    forward, backward = [c.split(") -> (") for c in sorted(calls, key=len)]
    # forward: q, k, v; backward: q, k, v and do in, dq partials, dk, dv out
    assert (forward[0].count(q), forward[0].count(kv)) == (1, 2)
    assert (backward[0].count(q), backward[0].count(kv)) == (2, 2)
    assert backward[1].count(kv) == 2
    halves = re.findall(rf"tensor<(?:\d+x)*{hd // 2}xf32>", text)
    assert halves == []
