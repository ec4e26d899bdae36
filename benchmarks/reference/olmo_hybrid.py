"""Plain reference for allenai's Olmo-Hybrid (``olmo_hybrid``): every layer
is a token mixer and a SwiGLU MLP, each under the Olmo 2 / Olmo 3 family's
norm *after* the sub-layer, ``x = x + norm(mixer(x)); x = x + norm(mlp(x))``,
the mixer the gated delta rule on the layers ``layer_types`` calls
``linear_attention`` and softmax attention on those it calls
``full_attention``, over a slice of the vocabulary.  ``norm`` is RMSNorm
with a weight at ``rms_norm_eps``; the sub-layers read the residual stream
itself (no pre-norm); no bias anywhere.

**linear_attention** (H = ``linear_num_value_heads`` =
``linear_num_key_heads`` heads, keys of dk = ``linear_key_head_dim``, values
of dv = ``linear_value_head_dim``, K = ``linear_conv_kernel_dim`` taps; u the
layer's input):

    q~, k~, v = silu(conv(u wq)), silu(conv(u wk)), silu(conv(u wv))
                                           causal depthwise, K shifted
                                           multiply-adds, zeros before the
                                           row, no bias
    q_t = q~_t / |q~_t| dk^-1/2,  k_t = k~_t / |k~_t|      a head (L2_EPS)
    g_t = -exp(A_log_h) softplus(u_t w_a + dt_bias_h)      a number a head
    beta_t = 2 sigmoid(u_t w_b)                            a head, in (0, 2)
    S_t = (I - beta_t k_t k_t^T) e^{g_t} S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                          S: dk x dv, S_0 = 0 a row
    y_t = concat_h(norm_dv(o_t) silu(u_t wg)) wo

The recurrence is computed **position by position** (``lax.scan`` over t):
no chunk, no cumulative sum, no triangular system.  (For the gradient the
scan is walked in runs of :data:`RUN` positions whose states are made again
in the backward, ``jax.checkpoint``: the same float32 operations a second
time, so that a layer keeps a state a run and not a state a position.)

**full_attention**: q, k, v, o without bias, ``num_attention_heads`` query
and ``num_key_value_heads`` key heads of ``hidden_size /
num_attention_heads``; q and k each under an RMSNorm with a weight over the
**whole projection**, before the heads are split; **no rotary embedding**
(``rope_parameters.rope_theta`` null); scale ``head_dim^-0.5``, causal
softmax one block of queries at a time.

**MLP**: ``down(silu(gate x) * up x)``, ``intermediate_size`` wide.

After the last layer a final norm and an untied head; the loss is the mean
next-token cross-entropy.

**Departures from the published description**, each an assumption the
configuration's ``assumed`` states (the catalog's row gives keys and a
phrase, not formulas): the gated-delta-net form of the linear layer with
``beta`` doubled by ``linear_allow_neg_eigval``; the silu output gate; the
norm after each sub-layer and the whole-projection QK-norm; no rotary
embedding; packed documents restart neither the state nor the convolution;
the logits are over a slice of the vocabulary.

float32 under ``default_matmul_precision("highest")``; nothing imported from
the program; it reads the program's parameter pytree (matrices input-major,
the layers of a kind stacked on a leading axis under ``gdn``, ``attn``,
``dense``), which is layout.  The layers are walked one by one, each half
recomputed in the backward (``jax.checkpoint``), as in
``reference/llama.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.llama import _attention, _rmsnorm

#: under the square root of the L2 norms (the ``fla`` kernels' l2norm)
L2_EPS = 1e-6
#: positions of a run of the recurrence whose states the backward makes again
RUN = 32


def conv(x, w):
    """x: (B, S, C); w: (K, C), tap K-1 the position itself."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, k:k + S] * w[k] for k in range(K))


def recurrence(q, k, v, g, beta):
    """q, k: (b, S, H, dk); v: (b, S, H, dv); g, beta: (b, S, H).  -> o (b,
    S, H, dv), one position after the other."""
    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None, None] * state     # (b, H, dk, dv)
        u = beta_t[..., None] * (
            v_t - jnp.einsum("bhde,bhd->bhe", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t)

    @jax.checkpoint
    def run(state, positions):
        return lax.scan(step, state, positions)

    b, S, H, dk = q.shape
    n = S // math.gcd(S, RUN)
    _, o = lax.scan(run, jnp.zeros((b, H, dk, v.shape[-1]), q.dtype), tuple(
        jnp.moveaxis(a, 1, 0).reshape(n, S // n, *a.shape[:1], *a.shape[2:])
        for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(S, *o.shape[2:]), 0, 1)


def l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def linear_attention(u, w, cfg):
    """The mixer of a ``linear_attention`` layer on the layer's input."""
    H, dk = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    b, S, _ = u.shape
    q, k, v = (jax.nn.silu(conv(u @ w["w" + n], w["conv_" + n])).reshape(
        b, S, H, -1) for n in "qkv")
    q, k = l2norm(q) * dk ** -0.5, l2norm(k)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(u @ w["w_a"] + w["dt_bias"])
    beta = 2.0 * jax.nn.sigmoid(u @ w["w_b"])
    o = recurrence(q, k, v, g, beta)
    o = _rmsnorm(o, w["head_norm"], cfg["rms_norm_eps"])
    return (o.reshape(b, S, -1) * jax.nn.silu(u @ w["wg"])) @ w["wo"]


def full_attention(u, w, cfg, q_block: int):
    """The mixer of a ``full_attention`` layer on the layer's input."""
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    eps = cfg["rms_norm_eps"]
    b, S, _ = u.shape
    q = _rmsnorm(u @ w["wq"], w["q_norm"], eps)
    k = _rmsnorm(u @ w["wk"], w["k_norm"], eps)
    v = (u @ w["wv"]).reshape(b, S, KV, hd)
    out = _attention(q.reshape(b, S, KV, H // KV, hd),
                     k.reshape(b, S, KV, hd), v, q_block)
    return out.reshape(b, S, H * hd) @ w["wo"]


def swiglu(u, w):
    return (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]


def logits(params, tokens, cfg, q_block=512):
    eps = cfg["rms_norm_eps"]
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = params["wte"][tokens]
    seen = {"gdn": 0, "attn": 0, "dense": 0}

    def row(stack):
        """The next layer of a kind's stack."""
        w = jax.tree.map(lambda a: a[seen[stack]], params[stack])
        seen[stack] += 1
        return w

    for kind in cfg["layer_types"][:cfg["num_hidden_layers"]]:
        if kind == "linear_attention":
            x = jax.checkpoint(lambda x, w: x + _rmsnorm(
                linear_attention(x, w, cfg), w["gdn_norm"], eps))(
                x, row("gdn"))
        else:
            x = jax.checkpoint(lambda x, w: x + _rmsnorm(
                full_attention(x, w, cfg, q_block), w["attn_norm"], eps))(
                x, row("attn"))
        x = jax.checkpoint(lambda x, w: x + _rmsnorm(
            swiglu(x, w), w["mlp_norm"], eps))(x, row("dense"))
    return _rmsnorm(x, params["final_norm"], eps) @ params["lm_head"].T


def loss(params, tokens, targets, cfg, q_block=512):
    with jax.default_matmul_precision("highest"):
        out = logits(params, tokens, cfg, q_block)
        lse = jax.nn.logsumexp(out, axis=-1)
        picked = jnp.take_along_axis(out, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)
