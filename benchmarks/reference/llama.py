"""Plain reference for the Llama-style dense decoder (Mistral-7B-v0.3).

Written from the published description (Touvron et al. 2023, "LLaMA"; Jiang
et al. 2023, "Mistral 7B"; the ``transformers`` ``MistralForCausalLM``
forward): token embedding, then per layer pre-RMSNorm, rotate-half RoPE on
queries and keys, causal grouped-query attention, residual, pre-RMSNorm,
SwiGLU (``down(silu(gate(x)) * up(x))``), residual; final RMSNorm, untied LM
head, mean next-token cross-entropy over every position.  No biases, no
sliding window (v0.3 has none).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, attention by blocks of queries
against all keys, no kernel, no cache, nothing imported from the program.
It reads the program's parameter pytree (matrices stored input-major and
stacked on a leading layer axis), which is layout, not arithmetic; layers are
walked with ``lax.scan`` so that under a sharded layout one layer's weights
are gathered at a time, and each layer is recomputed in the backward
(``jax.checkpoint`` around the plain layer: the same float32 operations a
second time), so that the gradient keeps one layer's scores, probabilities
and MLP intermediates and not every layer's.  At 12 layers on four chips the
stacked ones were refused by the v5e compiler ("Used 32.32G of 15.75G hbm",
ISSUE 30).  ``cfg`` holds the published keys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _rmsnorm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """x: (B, S, heads, head_dim); rotate-half form."""
    S, hd = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention(q, k, v, q_block):
    """q: (B, S, KV, G, hd); k, v: (B, S, KV, hd).  Causal softmax attention,
    one block of queries at a time against every key."""
    S, hd = q.shape[1], q.shape[-1]
    key_pos = jnp.arange(S)
    out = []
    for start in range(0, S, q_block):
        qb = q[:, start:start + q_block]
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qb, k) / jnp.sqrt(
            jnp.float32(hd))
        q_pos = start + jnp.arange(qb.shape[1])
        scores = jnp.where(key_pos[None, :] <= q_pos[:, None], scores,
                           -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("bkgqs,bskd->bqkgd", probs, v))
    return jnp.concatenate(out, axis=1)


def loss(params, tokens, targets, cfg, q_block=512):
    D, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or D // H
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    B, S = tokens.shape

    def layer(x, w):
        h = _rmsnorm(x, w["attn_norm"], eps)
        q = _rope((h @ w["wq"]).reshape(B, S, H, hd), theta)
        k = _rope((h @ w["wk"]).reshape(B, S, KV, hd), theta)
        v = (h @ w["wv"]).reshape(B, S, KV, hd)
        attn = _attention(q.reshape(B, S, KV, H // KV, hd), k, v, q_block)
        x = x + attn.reshape(B, S, H * hd) @ w["wo"]
        h = _rmsnorm(x, w["mlp_norm"], eps)
        x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
        return x, None

    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = params["wte"][tokens]
        x, _ = lax.scan(jax.checkpoint(layer), x, params["blocks"])
        x = _rmsnorm(x, params["final_norm"], eps)
        logits = x @ params["lm_head"].T
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)
