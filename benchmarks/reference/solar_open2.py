"""Plain reference for Upstage Solar-Open2 (``solar_open2``): every layer is a
token mixer and a mixture of experts under two pre-norms,
``x = x + mixer(norm(x)); x = x + experts(norm(x))``, the mixer softmax
attention on the layers ``gqa_layers`` names and gated delta-rule linear
attention with a per-channel decay (KDA) on the others, for one chip's share
of the heads, of the routed experts and of the vocabulary.  ``norm`` is
RMSNorm with a weight at ``rms_norm_eps``.

**KDA** (``linear_attn_config``: H = ``num_heads`` heads held here of d =
``head_dim``, K = ``short_conv_kernel_size`` taps; u the normed input):

    q~, k~, v~ = silu(conv(u wq)), silu(conv(u wk)), silu(conv(u wv))
                                           causal depthwise, K shifted
                                           multiply-adds, zeros before the
                                           row, no bias
    q_t = q~_t / |q~_t| d^-1/2,  k_t = k~_t / |k~_t|       a head (L2_EPS)
    g_t = -exp(A_log_h) softplus((u_t w_fa) w_fb + dt_bias)   (d,) a head
    beta_t = 2 sigmoid(u_t w_beta)                          a head
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                         S_0 = 0 a row
    y_t = concat_h(norm_d(o_t) sigmoid((u_t w_ga) w_gb + b_g)) wo

The recurrence is computed **position by position** (``lax.scan`` over t):
no chunk, no cumulative sum, no triangular system.

**Attention** (``gqa_layers``): q, k, v, o without bias, ``num_attention_heads``
query and ``num_key_value_heads`` key heads (those held here) of ``head_dim``,
scale ``head_dim^-0.5``, causal softmax one block of queries at a time, **no
rotary embedding** (``use_rope`` false), and with ``use_gqa_gate`` the output
gate ``(attn * sigmoid(u wg)) wo``, a channel each.

**Experts**: ``s = sigmoid(u router)`` over all ``n_routed_experts_published``
outputs; the ``num_experts_per_tok`` largest (ties to the lower id) are the
experts, their weights s divided by their sum (``norm_topk_prob``) times
``routed_scaling_factor``; expert e is ``(silu(u w_gate_e) * (u w_up_e))
w_down_e``; the layer's output is the weighted sum over the chosen experts
*held here* (``experts_held``: what the absent ones would add is left out)
plus the shared expert ``(silu(u shared_gate) * (u shared_up)) shared_down``.
Dense: every held expert is applied to every position and weighted by zero
where the position did not choose it.  No selection bias, no auxiliary loss.

**A share of the heads**: the reference is given the matrices of the heads
held here (the columns of wq, wk, wv, w_fb, w_gb, wg for them, wo's rows)
and computes their part of ``... wo``; what the absent heads would add is
left out, as with the experts.  With every head and every expert it is the
uncut layer.

After the last layer a final norm and an untied head; the loss is the mean
next-token cross-entropy.

float32 under ``default_matmul_precision("highest")``; nothing imported from
the program; it reads the program's parameter pytree (matrices input-major,
the layers of a kind stacked on a leading axis under ``kda``, ``attn``,
``experts``), which is layout.  The layers are one ``lax.scan`` whose step
picks its mixer by the layer's kind (so a configuration has layers of both
kinds); each mixer and each expert part is
recomputed in the backward (``jax.checkpoint``), as in ``reference/llama.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.llama import _attention, _rmsnorm

#: under the square root of the L2 norms (the ``fla`` kernels' l2norm)
L2_EPS = 1e-6


def conv(x, w):
    """x: (B, S, C); w: (K, C), tap K-1 the position itself."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, k:k + S] * w[k] for k in range(K))


def recurrence(q, k, v, g, beta):
    """q, k, v, g: (b, S, H, d); beta: (b, S, H).  -> o (b, S, H, d), one
    position after the other."""
    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None] * state           # (b, H, d, d)
        u = beta_t[..., None] * (
            v_t - jnp.einsum("bhde,bhd->bhe", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t)

    b, _, H, d = q.shape
    _, o = lax.scan(step, jnp.zeros((b, H, d, d), q.dtype),
                    tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda(u, w, cfg):
    H, d = (cfg["linear_attn_config"]["num_heads"],
            cfg["linear_attn_config"]["head_dim"])
    b, S, _ = u.shape
    q, k, v = (jax.nn.silu(conv(u @ w["w" + n], w["conv_" + n])).reshape(
        b, S, H, d) for n in "qkv")
    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        (u @ w["w_fa"]) @ w["w_fb"] + w["dt_bias"]).reshape(b, S, H, d)
    beta = 2.0 * jax.nn.sigmoid(u @ w["w_beta"])
    o = recurrence(q, k, v, g, beta)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                      + cfg["rms_norm_eps"]) * w["head_norm"]
    gate = jax.nn.sigmoid((u @ w["w_ga"]) @ w["w_gb"] + w["b_g"])
    return (o.reshape(b, S, H * d) * gate) @ w["wo"]


def attention(u, w, cfg, q_block):
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    b, S, _ = u.shape
    q = (u @ w["wq"]).reshape(b, S, KV, H // KV, hd)
    k = (u @ w["wk"]).reshape(b, S, KV, hd)
    v = (u @ w["wv"]).reshape(b, S, KV, hd)
    attn = _attention(q, k, v, q_block).reshape(b, S, H * hd)
    if cfg["use_gqa_gate"]:
        attn = attn * jax.nn.sigmoid(u @ w["wg"])
    return attn @ w["wo"]


def chosen(ranked_by, k):
    """ranked_by: (T, E) -> bool (T, E): is e among the token's k largest?
    By counting how many others the token prefers (ties to the lower id), a
    block of tokens at a time (``lax.map``: one compiled block, not T / 1024
    copies of it)."""
    index = jnp.arange(ranked_by.shape[-1])

    def token(p):
        mine, other = p[:, None], p[None, :]
        ahead = (other > mine) | ((other == mine) & (
            index[None, :] < index[:, None]))
        return jnp.sum(ahead, axis=-1) < k

    return lax.map(token, ranked_by,
                   batch_size=min(ranked_by.shape[0], 1024))


def experts(u, w, cfg):
    """u: (T, D) -> the held experts' part of the routed sum plus the shared
    expert, (T, D)."""
    first, stop = cfg["experts_held"]
    scores = jax.nn.sigmoid(u @ w["router"])
    weights = jnp.where(chosen(scores, cfg["num_experts_per_tok"]), scores,
                        0.0)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * cfg["routed_scaling_factor"]

    def expert(y, e):
        w_gate, w_up, w_down, weight = e
        return y + weight[:, None] * (
            (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down), None

    y, _ = lax.scan(expert, jnp.zeros_like(u),
                    (w["w_gate"], w["w_up"], w["w_down"],
                     weights.T[first:stop]))
    return y + (jax.nn.silu(u @ w["shared_gate"]) * (u @ w["shared_up"])) \
        @ w["shared_down"]


def logits(params, tokens, cfg, q_block=512):
    eps = cfg["rms_norm_eps"]
    b, S = tokens.shape
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = params["wte"][tokens]

    @jax.checkpoint
    def softmax_mixer(x, i):
        w = jax.tree.map(lambda a: a[i], params["attn"])
        return x + attention(_rmsnorm(x, w["attn_norm"], eps), w, cfg,
                             q_block)

    @jax.checkpoint
    def kda_mixer(x, i):
        w = jax.tree.map(lambda a: a[i], params["kda"])
        return x + kda(_rmsnorm(x, w["kda_norm"], eps), w, cfg)

    @jax.checkpoint
    def moe(x, w):
        return x + experts(_rmsnorm(x, w["mlp_norm"], eps).reshape(b * S, -1),
                           w, cfg).reshape(x.shape)

    def layer(x, at):
        softmax, i, w = at
        return moe(lax.cond(softmax, softmax_mixer, kda_mixer, x, i), w), None

    # One ``lax.scan`` over the layers, so that a mixer of each kind and the
    # experts compile once and not once a layer: a layer is its kind, its
    # place in its kind's stack, and its slice of the experts' stack.
    softmax = [i in cfg["gqa_layers"] for i in range(cfg["num_hidden_layers"])]
    place = [sum(k == kind for k in softmax[:i])
             for i, kind in enumerate(softmax)]
    x, _ = lax.scan(layer, x, (jnp.array(softmax), jnp.array(place),
                               params["experts"]))
    return _rmsnorm(x, params["final_norm"], eps) @ params["lm_head"].T


def loss(params, tokens, targets, cfg, q_block=512):
    with jax.default_matmul_precision("highest"):
        out = logits(params, tokens, cfg, q_block)
        lse = jax.nn.logsumexp(out, axis=-1)
        picked = jnp.take_along_axis(out, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)
