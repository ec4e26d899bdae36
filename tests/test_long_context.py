"""Context parallelism tests: ring attention + Ulysses vs dense reference.

(No reference counterpart exists — SURVEY §2.3/§5: the reference has no
native sequence parallelism.  Correctness target is the dense attention math
itself, forward AND backward, on the virtual 8-device mesh.)
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.ring_attention import (_xla_attention, ring_attention,
                                        ulysses_attention)
from ray_tpu.parallel import MeshSpec, make_mesh


@pytest.fixture(scope="module")
def seq_mesh():
    return make_mesh(MeshSpec(seq=8))


@pytest.fixture(scope="module")
def mixed_mesh():
    return make_mesh(MeshSpec(data=2, seq=4))


def _qkv(key, B=2, S=64, H=4, D=16, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(k, (B, S, H, D), dtype) for k in ks)


def _place(mesh, arrs):
    sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(("data", "fsdp"), "seq"))
    return tuple(jax.device_put(a, sh) for a in arrs)


# ------------------------------------------------------------------ forward
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_dense(seq_mesh, causal):
    q, k, v = _qkv(jax.random.key(0))
    expected = _xla_attention(q, k, v, causal=causal)
    q, k, v = _place(seq_mesh, (q, k, v))
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh=seq_mesh, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_ring_on_mixed_mesh(mixed_mesh):
    q, k, v = _qkv(jax.random.key(1), B=4, S=32)
    expected = _xla_attention(q, k, v, causal=True)
    qs, ks, vs = _place(mixed_mesh, (q, k, v))
    out = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=mixed_mesh))(
        qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_matches_dense(seq_mesh):
    q, k, v = _qkv(jax.random.key(2), H=8)  # heads % world == 0
    expected = _xla_attention(q, k, v, causal=True)
    qs, ks, vs = _place(seq_mesh, (q, k, v))
    out = jax.jit(lambda q, k, v: ulysses_attention(q, k, v, mesh=seq_mesh))(
        qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_rejects_indivisible_heads(seq_mesh):
    q, k, v = _qkv(jax.random.key(3), H=4)  # 4 heads on 8-way seq axis
    qs, ks, vs = _place(seq_mesh, (q, k, v))
    with pytest.raises(Exception):
        jax.jit(lambda q, k, v: ulysses_attention(q, k, v, mesh=seq_mesh))(
            qs, ks, vs)


# ----------------------------------------------------------------- backward
def test_ring_gradients_match_dense(seq_mesh):
    q, k, v = _qkv(jax.random.key(4))

    def dense_loss(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

    def ring_loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh=seq_mesh, causal=True) ** 2)

    expected = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    qs, ks, vs = _place(seq_mesh, (q, k, v))
    got = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(qs, ks, vs)
    for g, e in zip(got, expected):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=5e-4, atol=5e-4)


def test_ulysses_gradients_match_dense(seq_mesh):
    q, k, v = _qkv(jax.random.key(5), H=8)

    def dense_loss(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

    def uly_loss(q, k, v):
        return jnp.sum(ulysses_attention(q, k, v, mesh=seq_mesh) ** 2)

    expected = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    qs, ks, vs = _place(seq_mesh, (q, k, v))
    got = jax.jit(jax.grad(uly_loss, argnums=(0, 1, 2)))(qs, ks, vs)
    for g, e in zip(got, expected):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=5e-4, atol=5e-4)


# ------------------------------------------------------------ fused (pallas)
# S=1024 over 8 devices -> S_local=128, the smallest legal splash block, so
# these run the real fused path (interpret mode on the CPU mesh).
@pytest.mark.parametrize("causal", [True, False])
def test_fused_ring_matches_dense(seq_mesh, causal):
    q, k, v = _qkv(jax.random.key(6), B=1, S=1024, H=2, D=64)
    expected = _xla_attention(q, k, v, causal=causal)
    qs, ks, vs = _place(seq_mesh, (q, k, v))
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh=seq_mesh, causal=causal, impl="fused"))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_fused_ring_gradients_match_dense(seq_mesh):
    q, k, v = _qkv(jax.random.key(7), B=1, S=1024, H=2, D=64)

    def dense_loss(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

    def ring_loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh=seq_mesh, causal=True,
                                      impl="fused") ** 2)

    expected = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    qs, ks, vs = _place(seq_mesh, (q, k, v))
    got = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(qs, ks, vs)
    for g, e in zip(got, expected):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=5e-4, atol=5e-4)


def test_ring_auto_picks_fused_for_tileable_shards(seq_mesh):
    """impl='auto' must route S_local=128 shards to the fused body and tiny
    shards to the einsum body — both matching dense."""
    from ray_tpu.ops.ring_attention import _ring_block
    assert _ring_block(128) == 128
    assert _ring_block(1024) == 512
    assert _ring_block(8) is None
    q, k, v = _qkv(jax.random.key(8), B=1, S=1024, H=2, D=64)
    expected = _xla_attention(q, k, v, causal=True)
    qs, ks, vs = _place(seq_mesh, (q, k, v))
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh=seq_mesh, causal=True))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_fused_probe_passes_on_current_jax():
    from ray_tpu.ops import ring_attention as ra
    assert ra._probe_fused_surfaces() is True


def test_auto_downgrades_loudly_when_splash_surface_breaks(
        seq_mesh, monkeypatch):
    """If a jax upgrade breaks the private splash surfaces, impl='auto'
    must fall back to the einsum body (still correct) with ONE loud
    RuntimeWarning — not explode at trace time."""
    import warnings

    from ray_tpu.ops import ring_attention as ra

    def broken_kernel(*a, **kw):
        raise AttributeError("simulated splash surface rename")

    monkeypatch.setattr(ra, "_block_kernel", broken_kernel)
    monkeypatch.setattr(ra, "_FUSED_PROBE", None)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert ra._fused_available() is False
        assert ra._fused_available() is False  # cached: no second probe
    loud = [w for w in rec if issubclass(w.category, RuntimeWarning)]
    assert len(loud) == 1 and "einsum" in str(loud[0].message)

    # auto now routes tileable shards through einsum and still matches.
    q, k, v = _qkv(jax.random.key(10), B=1, S=1024, H=2, D=64)
    expected = _xla_attention(q, k, v, causal=True)
    qs, ks, vs = _place(seq_mesh, (q, k, v))
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh=seq_mesh, causal=True))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_splash_attention_matches_dense(causal, monkeypatch):
    """Single-device splash kernel (interpret on CPU), two blocks a side:
    causal AND the bidirectional FullMask path (previously
    NotImplementedError)."""
    from ray_tpu.ops import attention
    monkeypatch.setattr(attention, "_splash_kernel", functools.partial(
        attention._splash_kernel,
        blocks=attention.SplashBlocks.square(128)))
    q, k, v = _qkv(jax.random.key(9), B=1, S=256, H=2, D=64)
    expected = _xla_attention(q, k, v, causal=causal)
    out = jax.jit(lambda q, k, v: attention.splash_attention(
        q, k, v, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------- the dispatcher
@pytest.mark.parametrize("impl", ["xla", "splash", "ring", "ulysses"])
def test_dispatcher_serves_grouped_query_heads(seq_mesh, impl):
    """K and V at fewer heads than q (GQA): whichever implementation the
    string names, ``causal_attention`` equals the einsum on K and V repeated
    to the query heads, values and gradients (dk and dv summed over each
    group).  Splash runs interpreted here and takes K/V at their own count;
    the other three get the dispatcher's repeat."""
    import contextlib

    from ray_tpu.ops.attention import causal_attention

    B, S, H, KV, D = 1, 128, 8, 2, 64
    kq, kk, kv = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, KV, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, KV, D), jnp.float32)

    def with_grads(fn):
        def run(*args):
            out, vjp = jax.vjp(fn, *args)
            return (out, *vjp(out))
        return run

    want = jax.jit(with_grads(lambda q, k, v: _xla_attention(
        q, jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2),
        causal=True)))(q, k, v)
    sharded = impl in ("ring", "ulysses")
    args = _place(seq_mesh, (q, k, v)) if sharded else (q, k, v)
    with jax.set_mesh(seq_mesh) if sharded else contextlib.nullcontext():
        got = jax.jit(with_grads(
            lambda q, k, v: causal_attention(q, k, v, impl)))(*args)
    assert [g.shape for g in got] == [q.shape, q.shape, k.shape, v.shape]
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(g - w))) \
            < 1e-4 * float(jnp.max(jnp.abs(w)))


# ---------------------------------------------------------- GPT-2 integration
def test_gpt2_context_parallel_train_step():
    """Full GPT-2 train step with ring attention on a (data=2, seq=4) mesh:
    loss matches the xla-attention baseline and params update."""
    from ray_tpu.models import gpt2
    from ray_tpu.parallel import batch_sharding
    from ray_tpu.parallel.train_state import create_sharded_state, jit_train_step

    mesh = make_mesh(MeshSpec(data=2, seq=4))
    B, S = 4, 64
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 512, (B, S)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, 512, (B, S)), jnp.int32)

    losses = {}
    for impl in ("xla", "ring", "ulysses"):
        config = gpt2.GPTConfig(vocab_size=512, n_layer=2, n_head=4,
                                d_model=128, seq_len=S, attn_impl=impl,
                                dtype=jnp.float32, remat=False)
        optimizer = gpt2.make_optimizer(learning_rate=1e-3)
        params, opt_state = create_sharded_state(
            lambda key: gpt2.init_params(config, key),
            gpt2.logical_axes(config), mesh, jax.random.key(0), optimizer)
        step = jit_train_step(gpt2.make_train_step(config, optimizer),
                              mesh=mesh)
        sh = batch_sharding(mesh)
        t = jax.device_put(tokens, sh)
        y = jax.device_put(targets, sh)
        _, _, loss = step(params, opt_state, t, y)
        losses[impl] = float(loss)
    assert np.isfinite(list(losses.values())).all(), losses
    assert abs(losses["ring"] - losses["xla"]) < 1e-3, losses
    assert abs(losses["ulysses"] - losses["xla"]) < 1e-3, losses