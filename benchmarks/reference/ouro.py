"""Plain reference for the looped decoder (Ouro-2.6B; "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741): a stack of Llama
style layers with a norm on both sides of every sub-layer, run
``total_ut_steps`` times over with one set of weights, the final norm, the
head and an exit gate after every pass, and the paper's first-stage loss, an
entropy-regularised expectation over the pass a position leaves at.

Per position, ``RMSNorm_w(x) = x / sqrt(mean(x^2) + eps) * w``; layer ``l``
(four norm vectors: the published module's ``input_layernorm``,
``input_layernorm_2``, ``post_attention_layernorm``,
``post_attention_layernorm_2``):

    a = Attn_l(RMSNorm_n1(h))      q, k, v without bias, rotate-half RoPE on
                                   q and k, causal softmax(q.k / sqrt(hd)) v, Wo
    h = h + RMSNorm_n2(a)
    m = Wdown(silu(RMSNorm_n3(h) Wgate) * (RMSNorm_n3(h) Wup))
    h = h + RMSNorm_n4(m)

The loop, ``T`` passes over the same ``L`` layers:

    h^(0) = wte[token]
    x^(t) = Layer_L(.. Layer_1(h^(t-1)) ..);  h^(t) = RMSNorm_final(x^(t))
    logits^(t) = h^(t) Whead^T;  lam^(t) = sigmoid(h^(t) . w_gate + b_gate)

(the next pass reads the normed state: the configuration file's
``assumed``).  ``q_t = lam^(t) prod_{j<t} (1 - lam^(j))`` for ``t < T`` and
``q_T`` what is left; the loss is the mean over positions of ``sum_t q_t
CE_t - beta H(q)``, ``H(q) = -sum_t q_t log q_t`` with ``q`` clipped below
at 1e-20 inside the log.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, attention by blocks of queries
against all keys, the passes' heads one at a time, no kernel, nothing
imported from the program.  It reads the program's parameter pytree (matrices
input-major, the layers stacked on a leading axis; ``exit_gate`` one vector,
the gate's weight and then its bias), which is layout, not arithmetic.  The layers are walked
with ``lax.scan`` and each is recomputed in the backward (``jax.checkpoint``
around the plain layer: the same float32 operations a second time), so that
the gradient keeps one layer application's scores and MLP intermediates and
not all ``T x L`` of them.  ``cfg`` holds the published keys and
``exit_beta``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _rmsnorm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """x: (B, S, heads, head_dim); rotate-half form, pairs (i, i + hd/2)."""
    S, hd = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention(q, k, v, q_block):
    """q, k, v: (B, S, H, hd).  Causal softmax attention, one block of
    queries at a time against every key."""
    S, hd = q.shape[1], q.shape[-1]
    key_pos = jnp.arange(S)
    out = []
    for start in range(0, S, q_block):
        qb = q[:, start:start + q_block]
        scores = jnp.einsum("bqhd,bshd->bhqs", qb, k) / jnp.sqrt(
            jnp.float32(hd))
        q_pos = start + jnp.arange(qb.shape[1])
        scores = jnp.where(key_pos[None, :] <= q_pos[:, None], scores,
                           -jnp.inf)
        out.append(jnp.einsum("bhqs,bshd->bqhd",
                              jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out, axis=1)


def exit_distribution(lam):
    """lam: a list of the first T - 1 passes' gates.  -> the T
    probabilities of leaving at each pass."""
    q, reached = [], jnp.ones_like(lam[0])
    for gate in lam:
        q.append(gate * reached)
        reached = reached * (1.0 - gate)
    return q + [reached]


def loss(params, tokens, targets, cfg, q_block=512):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg["head_dim"]
    assert cfg["num_key_value_heads"] == H, "plain multi-head attention"
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    T, beta = cfg["total_ut_steps"], cfg["exit_beta"]
    B, S = tokens.shape

    def layer(h, w):
        u = _rmsnorm(h, w["attn_norm"], eps)
        q = _rope((u @ w["wq"]).reshape(B, S, H, hd), theta)
        k = _rope((u @ w["wk"]).reshape(B, S, H, hd), theta)
        v = (u @ w["wv"]).reshape(B, S, H, hd)
        a = _attention(q, k, v, q_block).reshape(B, S, H * hd) @ w["wo"]
        h = h + _rmsnorm(a, w["attn_norm_2"], eps)
        u = _rmsnorm(h, w["mlp_norm"], eps)
        m = (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]
        return h + _rmsnorm(m, w["mlp_norm_2"], eps), None

    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        w_gate, b_gate = params["exit_gate"][:-1], params["exit_gate"][-1]
        h = params["wte"][tokens]
        ce, lam = [], []
        for _ in range(T):
            x, _ = lax.scan(jax.checkpoint(layer), h, params["blocks"])
            h = _rmsnorm(x, params["final_norm"], eps)
            logits = h @ params["lm_head"].T
            picked = jnp.take_along_axis(logits, targets[..., None],
                                         axis=-1)[..., 0]
            ce.append(jax.nn.logsumexp(logits, axis=-1) - picked)
            lam.append(jax.nn.sigmoid(h @ w_gate + b_gate))
        q = exit_distribution(lam[:-1])  # the last gate decides nothing
        expected = sum(q_t * ce_t for q_t, ce_t in zip(q, ce))
        entropy = -sum(q_t * jnp.log(jnp.maximum(q_t, 1e-20)) for q_t in q)
        return jnp.mean(expected - beta * entropy)
