"""Plain reference for XingChen-AGI's Xing4.0-29B-A4B (``xing4_0``): the
``deepseek_v3`` family's layer (multi-head latent attention on every layer, a
dense SwiGLU MLP on the first ``first_k_dense_replace`` layers and a mixture
of experts on the others, one multi-token-prediction module) over a residual
of ``hc_mult`` streams under manifold-constrained hyper-connections (mHC,
arXiv:2512.24880, over Hyper-Connections, arXiv:2409.19606), for one chip's
share of the routed experts and of the vocabulary.  Written from ISSUE 62's
section 1; each departure from the two papers and the family's published
code is noted where it is made.

**The residual.**  ``n`` = ``hc_mult``, ``C`` = ``hidden_size``; ``X`` is
(n, C) a position.  Every sub-layer (a latent-attention mixer; a dense MLP or
an expert layer: a published layer is two) has maps of its own:

    x~      = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)   no weight
    H~_pre  = alpha_pre  (x~ phi_pre)  + b_pre                1 x n
    H~_post = alpha_post (x~ phi_post) + b_post               1 x n
    H~_res  = alpha_res  mat(x~ phi_res) + b_res              n x n
    H_pre   = sigmoid(H~_pre),   H_post = 2 sigmoid(H~_post)
    M_0     = exp(clip(H~_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    M_t     = T_r(T_c(M_{t-1})),  t = 1 .. hc_sinkhorn_iters
    u       = H_pre X;   y = f(RMSNorm(u));   X' = M_last X + H_post^T y

``T_c`` divides each column by (its sum + ``hc_eps``), ``T_r`` each row.
``phi`` holds the columns of ``phi_pre``, ``phi_post``, ``phi_res`` side by
side, ``alpha`` the three scalars, ``base`` the biases.  The published keys
name neither the order of ``T_c`` and ``T_r`` nor where ``hc_eps`` enters nor
a weight on the maps' norm: columns first, eps in the divisor, no weight
(``assumed`` in the configuration's file).  **The two ends**: the embedding is
copied to every stream; after the last layer the streams are summed, then
the final norm and the head (both papers' convention; the keys name neither).

**Latent attention** is ``reference/joyai_llm_flash.py``'s with
``rope_scaling`` of type ``yarn``: the rotary frequencies are
``transformers``' ``_compute_yarn_parameters`` written out
(:func:`yarn_inv_freq`), cos and sin are multiplied by ``m(mscale) /
m(mscale_all_dim)`` and the softmax's scale is ``qk_head_dim^-1/2 x
m(mscale_all_dim)^2``, ``m(x) = 0.1 x ln(factor) + 1``, as the
``deepseek_v3`` family's attention has it; the pairs are lanes (2i, 2i + 1).
**The dense MLP, the experts and their selection bias** are that file's.

**The prediction module** is the family's, applied a stream: ``Z[j] =
[norm(wte[t_{i+1}]) ; norm(X_L[j])] w_eh`` (one ``hidden_norm``, one
``w_eh``), one more latent-attention and expert layer under maps of its own
(the last two rows of ``hc``), the streams summed, its own final norm, the
shared head for the token two ahead.  ``loss = CE_main + mtp_loss_weight x
loss_mtp``.

float32 under ``default_matmul_precision("highest")``; nothing imported from
the program; it reads the program's parameter pytree (``mla``, ``dense``,
``experts``, ``mtp`` as the joyai reference does; ``hc``: a row a sub-layer
in the order they run, ``phi``'s rows stream-major), which is layout.  Each
sub-layer is recomputed in the backward (``jax.checkpoint``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.joyai_llm_flash import (cross_entropy, experts,
                                                  swiglu)
from benchmarks.reference.llama import _rmsnorm


def yarn_inv_freq(dim: int, base: float, scaling: dict) -> np.ndarray:
    """The ``dim // 2`` inverse frequencies of ``dim`` rotary lanes under
    ``rope_scaling`` of type ``yarn``, float32."""
    f32 = np.float32
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]
    pos_freqs = f32(base) ** (np.arange(0, dim, 2, dtype=f32) / f32(dim))
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=f32) - low) / (high - low),
                   0, 1)
    return (interpolation * ramp + extrapolation * (1 - ramp)).astype(f32)


def mscale(factor: float, by: float) -> float:
    return 0.1 * by * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope(x, inv_freq, scale):
    """x: (b, S, heads, r); lanes (2i, 2i + 1) are a pair, rotated by the
    position's angle at ``inv_freq[i]``, cos and sin times ``scale``."""
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq)[None, :]
    cos, sin = (f(angles)[None, :, None, :] * scale
                for f in (jnp.cos, jnp.sin))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def softmax_attention(q, k, v, scale: float, q_block: int):
    """q, k: (b, S, H, qk); v: (b, S, H, dv).  Causal, a block of queries at
    a time against every key."""
    S = q.shape[1]
    key_pos = jnp.arange(S)
    out = []
    for start in range(0, S, q_block):
        qb = q[:, start:start + q_block]
        scores = jnp.einsum("bqhd,bshd->bhqs", qb, k) * scale
        q_pos = start + jnp.arange(qb.shape[1])
        scores = jnp.where(key_pos[None, :] <= q_pos[:, None], scores,
                           -jnp.inf)
        out.append(jnp.einsum("bhqs,bshd->bqhd",
                              jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out, axis=1)


def attention(h, w, cfg, q_block):
    H, nope, rot, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    eps, rank = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    scaling = cfg["rope_scaling"]
    inv_freq = yarn_inv_freq(rot, cfg["rope_theta"], scaling)
    all_dim = mscale(scaling["factor"], scaling["mscale_all_dim"])
    turn = mscale(scaling["factor"], scaling["mscale"]) / all_dim
    b, S, _ = h.shape
    c_q = _rmsnorm(h @ w["wq_a"], w["q_norm"], eps)
    q = (c_q @ w["wq_b"]).reshape(b, S, H, nope + rot)
    down = h @ w["wkv_a"]
    c_kv = _rmsnorm(down[..., :rank], w["kv_norm"], eps)
    k_rot = rope(down[..., rank:].reshape(b, S, 1, rot), inv_freq, turn)
    up = (c_kv @ w["wkv_b"]).reshape(b, S, H, nope + dv)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], inv_freq, turn)],
                        axis=-1)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_rot, (b, S, H, rot))], axis=-1)
    o = softmax_attention(q, k, up[..., nope:],
                          (nope + rot) ** -0.5 * all_dim * all_dim, q_block)
    return o.reshape(b, S, H * dv) @ w["wo"]


def stream_maps(X, w, cfg):
    """X: (b, S, n, C) -> (H_pre (b, S, n), H_post (b, S, n), H_res (b, S,
    n, n)), the last after ``hc_sinkhorn_iters`` turns, each a division of
    the columns and then of the rows."""
    n = cfg["hc_mult"]
    b, S = X.shape[:2]
    flat = X.reshape(b, S, -1)
    normed = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                             + cfg["rms_norm_eps"])
    raw = normed @ w["phi"]
    a_pre, a_post, a_res = w["alpha"]
    base = w["base"]
    pre = jax.nn.sigmoid(a_pre * raw[..., :n] + base[:n])
    post = 2.0 * jax.nn.sigmoid(a_post * raw[..., n:2 * n] + base[n:2 * n])
    m = jnp.exp(jnp.clip(
        a_res * raw[..., 2 * n:] + base[2 * n:], cfg["mhc_h_res_clamp_min"],
        cfg["mhc_h_res_clamp_max"])).reshape(b, S, n, n)
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + cfg["hc_eps"])
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + cfg["hc_eps"])
    return pre, post, m


def hyper(X, w_hc, cfg, f):
    """One sub-layer: ``H_res X + H_post^T f(H_pre X)``."""
    pre, post, res = stream_maps(X, w_hc, cfg)
    y = f(jnp.einsum("bsn,bsnc->bsc", pre, X))
    return jnp.einsum("bsij,bsjc->bsic", res, X) \
        + post[..., None] * y[:, :, None, :]


def losses(params, tokens, targets, cfg, q_block=512):
    """-> (CE_main, loss_mtp)."""
    eps, n = cfg["rms_norm_eps"], cfg["hc_mult"]
    b, S = tokens.shape
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    def row(stack, i):
        return jax.tree.map(lambda a: a[i], params[stack])

    def sublayer(f):
        """``f``: (u, the kind's row) -> its output, under the maps."""
        return jax.checkpoint(lambda X, w, w_hc: hyper(
            X, w_hc, cfg, lambda u: f(u, w)))

    mixer = sublayer(lambda u, w: attention(
        _rmsnorm(u, w["attn_norm"], eps), w, cfg, q_block))
    dense = sublayer(lambda u, w: swiglu(
        _rmsnorm(u, w["mlp_norm"], eps), w["w_gate"], w["w_up"],
        w["w_down"]))

    def moe(layer):
        return sublayer(lambda u, w: experts(
            _rmsnorm(u, w["mlp_norm"], eps).reshape(b * S, -1), w, cfg,
            layer).reshape(u.shape))

    def block(X, i):
        """Published layer ``i`` (the module's is ``num_hidden_layers``)."""
        leading = cfg["first_k_dense_replace"]
        X = mixer(X, row("mla", i), row("hc", 2 * i))
        if i < leading:
            return dense(X, row("dense", i), row("hc", 2 * i + 1))
        return moe(i - leading)(X, row("experts", i - leading),
                                row("hc", 2 * i + 1))

    X = jnp.broadcast_to(params["wte"][tokens][:, :, None, :],
                         (b, S, n, cfg["hidden_size"]))
    for i in range(cfg["num_hidden_layers"]):
        X = block(X, i)
    head = params["lm_head"].T
    main = jnp.mean(cross_entropy(
        _rmsnorm(jnp.sum(X, axis=2), params["final_norm"], eps) @ head,
        targets))
    if not cfg["num_nextn_predict_layers"]:
        return main, jnp.zeros(())

    mtp = params["mtp"]
    ahead = _rmsnorm(params["wte"][targets], mtp["embed_norm"], eps)
    Z = jnp.concatenate(
        [jnp.broadcast_to(ahead[:, :, None, :], X.shape),
         _rmsnorm(X, mtp["hidden_norm"], eps)], axis=-1) @ mtp["w_eh"]
    Z = block(Z, cfg["num_hidden_layers"])
    out = _rmsnorm(jnp.sum(Z, axis=2), mtp["final_norm"], eps) @ head
    return main, jnp.mean(cross_entropy(out[:, :-1], targets[:, 1:]))


def loss(params, tokens, targets, cfg, q_block=512):
    with jax.default_matmul_precision("highest"):
        main, ahead = losses(params, tokens, targets, cfg, q_block)
        return main + cfg["mtp_loss_weight"] * ahead
