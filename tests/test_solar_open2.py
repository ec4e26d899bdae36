"""Upstage Solar-Open2 through ``models/hybrid.py``: KDA layers
(``models/kda.py`` over ``ops/kda.py``, the gated delta rule with a
per-channel decay), a gated attention layer without rotary embedding
(``models/layers.py``), and on every layer SwiGLU experts beside a SwiGLU
shared expert (``models/moe.py``), for one chip's share of the heads and of
the experts.

The plain reference is ``benchmarks/reference/solar_open2.py``, the one copy
(float32, the recurrence position by position, every held expert applied to
every position).  Everything runs on the CPU with seeded random weights at
tiny sizes, attention on the einsum path.  What every family is held to is
``tests/test_families.py``'s, by the row ``solar_open2``.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec
from benchmarks.reference import solar_open2 as reference
from ray_tpu.models import hybrid, kda as kda_model, moe
from ray_tpu.models.layers import attention
from ray_tpu.ops.kda import kda, kda_xla
from tests import families
from tests.families import rel_err


# ------------------------------------------------------- (1) the chunked scan
def _scan_inputs(chunks, chunk, decay, b=2, H=3, d=16):
    """Keys that resemble each other (a common part), ``beta`` above 1 on
    most positions, ``g`` summing to ``decay`` times a few a chunk."""
    S = chunks * chunk
    k = jax.random.split(jax.random.key(chunks * chunk), 5)
    return (reference.l2norm(jax.random.normal(k[0], (b, S, H, d))) * d ** -.5,
            reference.l2norm(jax.random.normal(k[1], (b, S, H, d)) + 0.5),
            jax.random.normal(k[2], (b, S, H, d)),
            -jax.nn.softplus(jax.random.normal(k[3], (b, S, H, d))) * decay,
            2.0 * jax.nn.sigmoid(jax.random.normal(k[4], (b, S, H)) + 1.0))


@pytest.mark.parametrize("scan", [kda_xla, kda],
                         ids=["the-xla-form", "as-dispatched"])
@pytest.mark.parametrize("chunks,chunk,decay", [
    (2, 64, 0.3), (3, 32, 0.3), (2, 64, 8.0), (3, 32, 8.0), (1, 16, 8.0),
    (2, 8, 8.0)])
def test_chunked_scan_is_the_recurrence(chunks, chunk, decay, scan):
    """Forward and every gradient (q, k, v, g, beta) of the XLA form, and of
    ``kda`` as it dispatches at these sizes (heads of 16: the XLA form; the
    kernels' sizes are ``tests/test_kda_kernel.py``'s), against a
    position-by-position ``lax.scan`` in float32, two rows a batch, with
    ``beta`` above 1 and, at ``decay`` 8, a ``g`` that sums below -88 inside
    a chunk, where a product of ratios would overflow: no inf, no nan."""
    args = _scan_inputs(chunks, chunk, decay)
    assert float(jnp.mean(args[4] > 1.0)) > 0.5
    if decay > 1 and chunk >= 32:
        sums = jnp.sum(args[3].reshape(2, chunks, chunk, 3, 16), axis=2)
        assert float(jnp.max(sums)) < -88  # exp(88) is no float32
    dy = jax.random.normal(jax.random.key(9), args[0].shape)

    def out_and_grads(fn):
        def run(*args):
            out, vjp = jax.vjp(fn, *args)
            return out, vjp(dy)
        return jax.jit(run)(*args)

    with jax.default_matmul_precision("highest"):
        got, grads = out_and_grads(lambda *a: scan(*a, chunk))
        want, grads_ref = out_and_grads(reference.recurrence)
    assert np.all(np.isfinite(got))
    assert rel_err(got, want) < 1e-5
    for name, g, g_ref in zip("qkvgb", grads, grads_ref):
        assert np.all(np.isfinite(g)), name
        assert rel_err(g, g_ref) < 1e-4, name


def test_scan_products_are_in_the_inputs_dtype():
    """bf16 in: bf16 products with float32 accumulation, within bf16's
    rounding of the float32 recurrence."""
    q, k, v, g, beta = _scan_inputs(3, 32, 0.3)
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v))
    got = jax.jit(lambda *a: kda(*a, 32))(*low, g, beta)
    assert got.dtype == jnp.bfloat16
    assert rel_err(got, reference.recurrence(q, k, v, g, beta)) < 0.05


def test_the_state_is_zero_before_a_rows_first_position():
    """Row 1's output does not depend on row 0, and position 0's output is
    ``beta (k . q) v`` of that position alone."""
    q, k, v, g, beta = _scan_inputs(2, 16, 0.3)
    scan = jax.jit(lambda *a: kda(*a, 16))
    with jax.default_matmul_precision("highest"):
        both = scan(q, k, v, g, beta)
        alone = scan(*(a[1:] for a in (q, k, v, g, beta)))
    assert rel_err(both[1:], alone) < 1e-6
    first = beta[:, 0, :, None] * jnp.sum(k[:, 0] * q[:, 0], -1,
                                          keepdims=True) * v[:, 0]
    assert rel_err(both[:, 0], first) < 1e-5


# ------------------------------------------------------------- (2) the mixer
def _kda_parts(heads, dtype=jnp.float32, d_model=64):
    config = families.preset("solar_open2", kda_heads=heads,
                             d_model=d_model, dtype=dtype)
    blk = jax.tree.map(lambda a: a[0], kda_model.init_params(
        config, jax.random.key(0), 1, 0.05))
    # every vector away from its start and the projections large enough
    # that the decay and beta differ by position
    noise = iter(jax.random.split(jax.random.key(1), len(blk)))
    blk = {name: a + 0.1 * jax.random.normal(next(noise), a.shape)
           if a.ndim == 1 else a * 5.0 for name, a in blk.items()}
    x = jax.random.normal(jax.random.key(2), (2, 64, d_model), dtype)
    cfg = {"linear_attn_config": {"num_heads": heads,
                                  "head_dim": config.kda_head_dim},
           "rms_norm_eps": config.rms_eps}
    return config, blk, x, cfg


def _normed(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight


def test_mixer_matches_the_reference():
    config, blk, x, cfg = _kda_parts(4)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda x, blk: kda_model.mixer(
            x, blk, config, kda_model.logical_axes(config)))(x, blk) - x
        want = jax.jit(lambda x, blk: reference.kda(
            _normed(x, blk["kda_norm"], config.rms_eps), blk, cfg))(x, blk)
    assert rel_err(got, want) < 1e-5


def test_beta_may_pass_one_and_the_decay_is_a_channels_own():
    """``beta`` = 2 sigmoid: above 1 on some positions (an eigenvalue of
    ``I - beta k k^T`` is then negative); and a channel's decay reaches that
    channel of the state alone: changing ``dt_bias`` at channel c of head 0
    changes nothing that head 1 puts out."""
    config, blk, x, cfg = _kda_parts(2)
    u = _normed(x, blk["kda_norm"], config.rms_eps)
    beta = 2.0 * jax.nn.sigmoid(u @ blk["w_beta"])
    assert 0.1 < float(jnp.mean(beta > 1.0)) < 0.9
    d = config.kda_head_dim
    moved = dict(blk, dt_bias=blk["dt_bias"].at[3].add(2.0))
    only_head_1 = dict(wo=blk["wo"].at[:d].set(0.0))
    layer = jax.jit(lambda w: reference.kda(u, w, cfg))
    with jax.default_matmul_precision("highest"):
        a, b = (layer(dict(w, **only_head_1)) for w in (blk, moved))
        c, e = (layer(w) for w in (blk, moved))
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.allclose(np.asarray(c), np.asarray(e))


def test_the_convolutions_are_causal():
    config, blk, x, _ = _kda_parts(2)
    axes = kda_model.logical_axes(config)
    t = 37
    moved = x.at[:, t].add(1.0)
    a, b = map(jax.jit(lambda v: kda_model.mixer(v, blk, config, axes)),
               (x, moved))
    assert np.array_equal(np.asarray(a[:, :t]), np.asarray(b[:, :t]))
    assert not np.allclose(np.asarray(a[:, t]), np.asarray(b[:, t]))


# ----------------------------------------------------- (3) the shares add up
def _head_columns(name, a, first, stop, d):
    """The part of one KDA or attention leaf that heads [first, stop) own:
    columns of the head-wide projections, rows of ``wo``, all of what every
    head reads."""
    if name in ("wq", "wk", "wv", "w_fb", "w_gb", "b_g", "dt_bias", "wg",
                "conv_q", "conv_k", "conv_v"):
        return a[..., first * d:stop * d]
    if name == "wo":
        return a[first * d:stop * d]
    if name in ("w_beta", "A_log"):
        return a[..., first:stop]
    return a  # the pre-norm, the low-rank halves w_fa and w_ga, head_norm


@pytest.mark.parametrize("shares", [2, 8])
def test_the_kda_head_shares_add_up_to_the_uncut_mixer(shares):
    """8 heads cut in ``shares``: the mixers' outputs over the shares, each
    on its own heads' columns and ``wo``'s rows for them, sum to the uncut
    reference's mixer."""
    config, blk, x, cfg = _kda_parts(8)
    d, held = config.kda_head_dim, 8 // shares
    part = dataclasses.replace(config, kda_heads=held)
    axes = kda_model.logical_axes(config)
    mixer = jax.jit(lambda mine: kda_model.mixer(x, mine, part, axes) - x)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda blk: reference.kda(
            _normed(x, blk["kda_norm"], config.rms_eps), blk, cfg))(blk)
        total = jnp.zeros_like(x)
        for share in range(shares):
            total = total + mixer({
                name: _head_columns(name, a, share * held,
                                    (share + 1) * held, d)
                for name, a in blk.items()})
    assert rel_err(total, want) < 1e-5


@pytest.mark.parametrize("shares", [2, 4])
def test_the_attention_head_shares_add_up_to_the_uncut_layer(shares):
    """8 query heads over 4 KV heads, gated, no rotary, cut in ``shares``
    (a share: whole KV heads with the query heads that read them)."""
    H, KV, hd, D = 8, 4, 16, 64
    config = families.float32("solar_open2", n_head=H, n_kv_head=KV,
                              head_dim=hd, d_model=D)
    blk = jax.tree.map(lambda a: a[0] * 5.0, hybrid.init_params(
        config, jax.random.key(0))["attn"])
    assert set(blk) == {"attn_norm", "wq", "wk", "wv", "wo", "wg"}
    axes = hybrid.logical_axes(config)["attn"]
    x = jax.random.normal(jax.random.key(1), (2, 64, D))
    cfg = {"num_attention_heads": H, "num_key_value_heads": KV,
           "head_dim": hd, "use_gqa_gate": True}
    with jax.default_matmul_precision("highest"):
        want = reference.attention(
            _normed(x, blk["attn_norm"], config.rms_eps), blk, cfg, 64)
        total = jnp.zeros_like(x)
        for share in range(shares):
            q0, q1 = share * H // shares, (share + 1) * H // shares
            k0, k1 = share * KV // shares, (share + 1) * KV // shares
            mine = {"attn_norm": blk["attn_norm"],
                    "wq": blk["wq"][:, q0 * hd:q1 * hd],
                    "wg": blk["wg"][:, q0 * hd:q1 * hd],
                    "wk": blk["wk"][:, k0 * hd:k1 * hd],
                    "wv": blk["wv"][:, k0 * hd:k1 * hd],
                    "wo": blk["wo"][q0 * hd:q1 * hd]}
            part = dataclasses.replace(config, n_head=q1 - q0,
                                       n_kv_head=k1 - k0)
            total = total + jax.jit(lambda mine: attention(
                x, mine, part, axes))(mine) - x
    assert rel_err(total, want) < 1e-5
    # the gate is no no-op: without it the layer is another
    plain = dict(cfg, use_gqa_gate=False)
    assert rel_err(reference.attention(
        _normed(x, blk["attn_norm"], config.rms_eps), blk, plain, 64),
        want) > 0.1


@pytest.mark.parametrize("family,E,k,shares", [
    ("solar_open2", 16, 3, 2), ("solar_open2", 16, 3, 4),
    ("solar_open2", 16, 3, 16),
    # JoyAI-LLM-Flash's layer: 256 outputs, 8 a token, sixteen shares of 16,
    # a selection bias that picks and does not weigh, weights times 2.5
    ("joyai_llm_flash", 256, 8, 16)],
    ids=["2", "4", "16", "joyai-16-of-256"])
def test_the_expert_shares_add_up_to_the_uncut_layer(family, E, k, shares):
    """``E`` SwiGLU experts cut in ``shares``: the routed parts that all the
    shares give, plus the SwiGLU shared expert counted once, are the uncut
    reference's layer."""
    D, F = 32, 24
    ks = jax.random.split(jax.random.key(shares), 8)
    whole = {"router": jax.random.normal(ks[0], (D, E)) * 0.5,
             "w_gate": jax.random.normal(ks[1], (E, D, F)) * 0.2,
             "w_up": jax.random.normal(ks[2], (E, D, F)) * 0.2,
             "w_down": jax.random.normal(ks[3], (E, F, D)) * 0.2,
             "shared_gate": jax.random.normal(ks[4], (D, F)) * 0.2,
             "shared_up": jax.random.normal(ks[5], (D, F)) * 0.2,
             "shared_down": jax.random.normal(ks[6], (F, D)) * 0.2}
    h = jax.random.normal(ks[7], (2, 64, D))
    cfg = {"experts_held": [0, E], "num_experts_per_tok": k,
           "norm_topk_prob": True, "routed_scaling_factor": 1,
           "n_routed_experts_published": E}
    plain, more = spec.load_module("reference", family), {}
    if family == "joyai_llm_flash":
        cfg.update(routed_scaling_factor=2.5, router_bias_seed=5,
                   router_bias_std=0.1)
        more = {"bias": plain.selection_bias(cfg, 2), "scale": 2.5}
        uncut = functools.partial(plain.experts, layer=2)
    else:
        uncut = plain.experts
    layer = jax.jit(lambda blk, first: moe.moe_mlp(
        h, blk, experts_per_token=k, norm_topk_prob=True, dtype=jnp.float32,
        first_held=first, scoring="sigmoid", **more)[0], static_argnums=1)
    with jax.default_matmul_precision("highest"):
        want = uncut(h.reshape(-1, D), whole, cfg)
        held = E // shares
        total = jnp.zeros_like(h)
        for share in range(shares):
            first = share * held
            blk = {"router": whole["router"]}
            blk.update({name: whole[name][first:first + held]
                        for name in ("w_gate", "w_up", "w_down")})
            if share == 0:  # what every chip computes alike, counted once
                blk.update({name: whole[name] for name in (
                    "shared_gate", "shared_up", "shared_down")})
            total = total + layer(blk, first)
    assert rel_err(total.reshape(-1, D), want) < 1e-5
