"""Plain reference for GPT-2 (Radford et al. 2019; the ``transformers``
``GPT2LMHeadModel`` forward).

Token plus learned position embedding, then per layer pre-LayerNorm (eps
1e-5, scale and bias), causal multi-head attention with biased qkv and output
projections, residual, pre-LayerNorm, MLP with the tanh-approximated GELU
(``gelu_new``), residual; final LayerNorm, LM head tied to the token
embedding, mean next-token cross-entropy over every position.

One departure from the published model, shared with the program: the
embedding holds ``padded_vocab_size`` rows (50304 for 50257) and the softmax
runs over all of them; ids are only ever drawn from the published 50257.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``, attention by blocks of queries
against all keys, no kernel, nothing imported from the program.  It reads the
program's parameter pytree (matrices input-major, stacked on a leading layer
axis; layout, not arithmetic).  ``cfg`` holds the published keys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _layernorm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def _attention(q, k, v, q_block):
    """q, k, v: (B, S, H, hd)."""
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
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("bhqs,bshd->bqhd", probs, v))
    return jnp.concatenate(out, axis=1)


def loss(params, tokens, targets, cfg, q_block=512):
    D, H = cfg["n_embd"], cfg["n_head"]
    eps = cfg["layer_norm_epsilon"]
    B, S = tokens.shape

    def layer(x, w):
        h = _layernorm(x, w["ln1_scale"], w["ln1_bias"], eps)
        qkv = h @ w["qkv_w"] + w["qkv_b"]
        q, k, v = (t.reshape(B, S, H, D // H)
                   for t in jnp.split(qkv, 3, axis=-1))
        attn = _attention(q, k, v, q_block).reshape(B, S, D)
        x = x + attn @ w["out_w"] + w["out_b"]
        h = _layernorm(x, w["ln2_scale"], w["ln2_bias"], eps)
        h = jax.nn.gelu(h @ w["mlp_in_w"] + w["mlp_in_b"], approximate=True)
        return x + h @ w["mlp_out_w"] + w["mlp_out_b"], None

    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = params["wte"][tokens] + params["wpe"][:S]
        x, _ = lax.scan(layer, x, params["blocks"])
        x = _layernorm(x, params["lnf_scale"], params["lnf_bias"], eps)
        logits = x @ params["wte"].T
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)
