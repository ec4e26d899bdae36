"""GPT-2 model + mesh/sharding tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import gpt2
from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh, logical_to_spec
from ray_tpu.parallel.train_state import create_sharded_state, jit_train_step


@pytest.fixture(scope="module")
def tiny():
    return gpt2.GPTConfig.tiny()


def test_forward_shapes(tiny):
    params = gpt2.init_params(tiny, jax.random.key(0))
    tokens = jnp.zeros((2, tiny.seq_len), jnp.int32)
    logits = gpt2.forward(params, tokens, tiny)
    assert logits.shape == (2, tiny.seq_len, tiny.vocab_size)
    assert logits.dtype == jnp.float32


def test_causality(tiny):
    """Changing a future token must not affect earlier logits."""
    config = gpt2.GPTConfig(vocab_size=256, n_layer=1, n_head=2, d_model=64,
                            seq_len=32, remat=False, attn_impl="xla")
    params = gpt2.init_params(config, jax.random.key(1))
    rng = np.random.default_rng(0)
    t1 = rng.integers(0, 256, (1, 32))
    t2 = t1.copy()
    t2[0, -1] = (t2[0, -1] + 1) % 256
    l1 = gpt2.forward(params, jnp.asarray(t1, jnp.int32), config)
    l2 = gpt2.forward(params, jnp.asarray(t2, jnp.int32), config)
    np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=1e-4)
    assert not np.allclose(l1[0, -1], l2[0, -1], atol=1e-4)


def test_num_params_matches(tiny):
    params = gpt2.init_params(tiny, jax.random.key(0))
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert actual == gpt2.num_params(tiny)


def test_loss_decreases_training(tiny):
    optimizer = gpt2.make_optimizer(learning_rate=1e-2)
    params = gpt2.init_params(tiny, jax.random.key(0))
    opt_state = optimizer.init(params)
    step = jax.jit(gpt2.make_train_step(tiny, optimizer))
    rng = np.random.default_rng(0)
    # Learnable pattern: repeat tokens.
    seq = np.tile(rng.integers(0, tiny.vocab_size, (1, 8)), (4, tiny.seq_len // 8 + 1))
    toks = jnp.asarray(seq[:, : tiny.seq_len + 1], jnp.int32)
    first = None
    for i in range(10):
        params, opt_state, loss = step(params, opt_state, toks[:, :-1], toks[:, 1:])
        if first is None:
            first = float(loss)
    assert float(loss) < first * 0.8


def test_sharded_train_step_dp_tp():
    """Full train step jitted over a (data=2, fsdp=2, tensor=2) mesh."""
    config = gpt2.GPTConfig(vocab_size=512, n_layer=2, n_head=4, d_model=128,
                            seq_len=64, attn_impl="xla")
    spec = MeshSpec(data=2, fsdp=2, tensor=2)
    mesh = make_mesh(spec)
    optimizer = gpt2.make_optimizer(learning_rate=1e-3)
    params, opt_state = create_sharded_state(
        lambda k: gpt2.init_params(config, k), gpt2.logical_axes(config),
        mesh, jax.random.key(0), optimizer)
    # Params actually sharded: qkv_w split over fsdp (embed) and tensor (heads).
    qkv_sharding = params["blocks"]["qkv_w"].sharding
    assert qkv_sharding.spec == logical_to_spec(("layers", "embed", "heads"))
    step = jit_train_step(gpt2.make_train_step(config, optimizer))
    sh = batch_sharding(mesh)
    rng = np.random.default_rng(0)
    t = jnp.asarray(rng.integers(0, config.vocab_size, (8, config.seq_len + 1)), jnp.int32)
    tokens = jax.device_put(t[:, :-1], sh)
    targets = jax.device_put(t[:, 1:], sh)
    params, opt_state, loss = step(params, opt_state, tokens, targets)
    assert np.isfinite(float(loss))


def test_sharded_matches_single_device():
    """The distributed step computes the same loss as single-device."""
    config = gpt2.GPTConfig(vocab_size=256, n_layer=1, n_head=2, d_model=64,
                            seq_len=32, remat=False, attn_impl="xla")
    optimizer = gpt2.make_optimizer(learning_rate=1e-3)
    rng = np.random.default_rng(0)
    t = jnp.asarray(rng.integers(0, 256, (4, 33)), jnp.int32)

    params1 = gpt2.init_params(config, jax.random.key(0))
    loss1 = float(gpt2.loss_fn(params1, t[:, :-1], t[:, 1:], config))

    mesh = make_mesh(MeshSpec(data=4, tensor=2))
    params2, _ = create_sharded_state(
        lambda k: gpt2.init_params(config, k), gpt2.logical_axes(config),
        mesh, jax.random.key(0), None)
    sh = batch_sharding(mesh)
    tokens = jax.device_put(t[:, :-1], sh)
    targets = jax.device_put(t[:, 1:], sh)
    loss2 = float(jax.jit(
        lambda p, x, y: gpt2.loss_fn(p, x, y, config))(params2, tokens, targets))
    # bf16 compute (config.dtype): sharded matmuls reduce in a different
    # order than single-device, so losses differ by a few bf16 ULPs
    # (~2.4e-3 observed on installed jax); fp32 would hold 2e-3.
    rtol = 2e-3 if config.dtype == jnp.float32 else 8e-3
    np.testing.assert_allclose(loss1, loss2, rtol=rtol)


def test_mesh_spec_validation():
    with pytest.raises(ValueError):
        make_mesh(MeshSpec(data=100))
    spec = MeshSpec.auto(8, tensor=2)
    assert spec.data == 4 and spec.size == 8


def test_graft_entry_dryrun():
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)


@pytest.mark.parametrize("kw", [
    {"remat_policy": "attn_outside"},
    {"remat_policy": "attn_outside", "scan_layers": False},
    {"scan_layers": False},
    {"remat": False},
], ids=lambda kw: ",".join(f"{k}={v}" for k, v in kw.items()))
def test_attn_outside_unrolled_and_no_remat_match_scan_block(kw):
    """remat_policy='attn_outside' (the two halves of a block checkpointed
    around attention), scan_layers=False (the Python loop over the layers)
    and remat=False are pure schedule changes: loss and grads must match
    the default, remat_policy='block' under lax.scan."""
    import dataclasses

    base = gpt2.GPTConfig.tiny()
    assert base.remat and base.remat_policy == "block" and base.scan_layers
    key = jax.random.PRNGKey(0)
    params = gpt2.init_params(base, key)
    tok = jax.random.randint(key, (2, base.seq_len), 0, base.vocab_size)
    tgt = jax.random.randint(key, (2, base.seq_len), 0, base.vocab_size)

    ref_l, ref_g = jax.value_and_grad(gpt2.loss_fn)(params, tok, tgt, base)
    cfg = dataclasses.replace(base, **kw)
    loss, grads = jax.value_and_grad(gpt2.loss_fn)(params, tok, tgt, cfg)
    assert abs(float(loss) - float(ref_l)) < 1e-5
    err = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), grads, ref_g)))
    # bf16 activations quantize grads to ~2^-10 ULPs at these magnitudes
    # and the schedules reorder bf16 reductions (9.8e-4 observed on
    # installed jax); fp32 would hold the original 1e-4.
    assert err < 2e-3, err


def test_remat_policy_is_one_of_two(tiny):
    import dataclasses

    config = dataclasses.replace(tiny, remat_policy="dots")
    assert gpt2.REMAT_POLICIES == ("block", "attn_outside")
    with pytest.raises(ValueError, match="'block' or 'attn_outside'"):
        gpt2.forward_hidden(gpt2.init_params(config, jax.random.key(0)),
                            jnp.zeros((1, config.seq_len), jnp.int32), config)


@pytest.mark.parametrize("package,forbidden", [
    ("models", ("ray_tpu.models.gpt2", "ray_tpu.models.llama",
                "ray_tpu.models.hybrid")),
    ("ops", ("ray_tpu.models",)),
])
def test_imports_point_down(package, forbidden):
    """No model file imports another decoder (the arrows inside the package
    point at what is no decoder: llama and hybrid -> layers -> moe, hybrid ->
    mamba2 -> layers, hybrid -> kda -> mamba2 and layers), and nothing under
    ops/ reaches up into models/."""
    import ast
    import os

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "ray_tpu", package)
    seen = 0
    for name in sorted(os.listdir(root)):
        if not name.endswith(".py") or name == "__init__.py":
            continue
        seen += 1
        with open(os.path.join(root, name)) as f:
            tree = ast.parse(f.read())
        imported = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                imported.update(a.name for a in node.names)
            elif isinstance(node, ast.ImportFrom):
                here = ["ray_tpu", package][:3 - node.level] \
                    if node.level else []
                module = ".".join(here + ([node.module] if node.module
                                          else []))
                imported.add(module)
                imported.update(f"{module}.{a.name}" for a in node.names)
        bad = sorted(m for m in imported
                     if any(m == f or m.startswith(f + ".")
                            for f in forbidden))
        assert not bad, f"ray_tpu/{package}/{name} imports {bad}"
    assert seen >= 4
