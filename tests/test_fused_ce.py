"""Equivalence tests for the fused LM-head cross-entropy kernel
(ops/fused_ce.py) against the dense logsumexp path, fwd + bwd, in pallas
interpret mode on CPU (the real-TPU numbers live in PERF.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.fused_ce import fused_lm_head_ce


def _dense_ce(x, wte, targets):
    logits = jnp.einsum("bsd,vd->bsv", x, wte.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


@pytest.mark.parametrize("bwd_impl", ["pallas", "xla"])
def test_fused_ce_matches_dense_fwd_bwd(bwd_impl):
    key = jax.random.PRNGKey(0)
    B, S, D, V = 2, 64, 32, 256
    kx, kw, kt = jax.random.split(key, 3)
    x = jax.random.normal(kx, (B, S, D), jnp.float32)
    w = jax.random.normal(kw, (V, D), jnp.float32) * 0.05
    t = jax.random.randint(kt, (B, S), 0, V)

    ref_loss, (ref_dx, ref_dw) = jax.value_and_grad(_dense_ce, argnums=(0, 1))(
        x, w, t)
    fused_loss, (dx, dw) = jax.value_and_grad(
        lambda a, b: fused_lm_head_ce(a, b, t, bwd_impl=bwd_impl),
        argnums=(0, 1))(x, w)

    np.testing.assert_allclose(fused_loss, ref_loss, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dx, ref_dx, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(dw, ref_dw, rtol=1e-4, atol=1e-5)


def test_fused_ce_bf16_close_to_fp32_dense():
    key = jax.random.PRNGKey(1)
    B, S, D, V = 2, 32, 64, 512
    kx, kw, kt = jax.random.split(key, 3)
    x = jax.random.normal(kx, (B, S, D), jnp.bfloat16)
    w = (jax.random.normal(kw, (V, D), jnp.float32) * 0.05)
    t = jax.random.randint(kt, (B, S), 0, V)

    ref = _dense_ce(x.astype(jnp.float32), w, t)
    fused = fused_lm_head_ce(x, w, t)
    assert abs(float(fused) - float(ref)) < 0.05


def test_cost_model_and_gpt2_auto_dispatch():
    """loss_impl='auto' flips to the fused kernel exactly when the
    roofline model predicts a win (small D / fp32 logits), and the fused
    GPT-2 loss matches the dense path."""
    from ray_tpu.models import gpt2
    from ray_tpu._private.accelerators import device_peaks
    from ray_tpu.ops.fused_ce import fused_ce_wins

    # The model's documented regime boundaries (v5e constants).
    v5e = device_peaks("TPU v5 lite")
    assert not fused_ce_wins(768, 2, v5e)   # GPT-2-small bf16: dense
    assert not fused_ce_wins(768, 4, v5e)   # GPT-2-small fp32: dense
    assert fused_ce_wins(128, 4, v5e)       # small head, exact softmax: fused
    assert not fused_ce_wins(512, 2, v5e)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 128, (2, 32)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, 128, (2, 32)), jnp.int32)
    base = dict(vocab_size=128, n_layer=1, n_head=2, d_model=32,
                seq_len=32, dtype=jnp.float32, remat=False,
                logits_dtype=jnp.float32)
    cfg_fused = gpt2.GPTConfig(**base, loss_impl="fused")
    cfg_dense = gpt2.GPTConfig(**base, loss_impl="dense")
    # auto is additionally gated on default_backend()=='tpu' (interpret-
    # mode pallas off-TPU would be a silent slowdown), so on this CPU
    # mesh it must resolve to dense; forced 'fused' still runs (interpret).
    cfg_auto = gpt2.GPTConfig(**base)
    assert cfg_auto.loss_impl == "auto"
    params = gpt2.init_params(cfg_dense, jax.random.key(0))
    l_dense = gpt2.loss_fn(params, tokens, targets, cfg_dense)
    for cfg in (cfg_fused, cfg_auto):
        l = gpt2.loss_fn(params, tokens, targets, cfg)
        np.testing.assert_allclose(float(l), float(l_dense),
                                   rtol=1e-5, atol=1e-5)
    import pytest as _pytest
    with _pytest.raises(ValueError, match="loss_impl"):
        gpt2.loss_fn(params, tokens, targets,
                     gpt2.GPTConfig(**base, loss_impl="Fused"))


def test_fused_ce_under_jit_and_odd_blocks():
    key = jax.random.PRNGKey(2)
    B, S, D, V = 1, 24, 16, 96  # deliberately non-power-of-two row count
    kx, kw, kt = jax.random.split(key, 3)
    x = jax.random.normal(kx, (B, S, D), jnp.float32)
    w = jax.random.normal(kw, (V, D), jnp.float32) * 0.1
    t = jax.random.randint(kt, (B, S), 0, V)
    f = jax.jit(lambda a, b, c: fused_lm_head_ce(a, b, c))
    np.testing.assert_allclose(f(x, w, t), _dense_ce(x, w, t),
                               rtol=1e-5, atol=1e-5)
