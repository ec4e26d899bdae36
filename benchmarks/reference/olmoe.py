"""Plain reference for OLMoE-1B-7B (Muennighoff et al. 2024, "OLMoE: Open
Mixture-of-Experts Language Models"; the ``transformers``
``OlmoeForCausalLM`` forward).

Llama's pre-norm decoder with two changes.  (1) QK-norm: an RMSNorm over the
whole query projection and one over the whole key projection, before the
heads are split and RoPE is applied.  (2) The MLP is a mixture of experts:
router logits ``h @ router``, softmax over all experts in float32, the
``num_experts_per_tok`` largest probabilities choose the experts and are the
combine weights as they stand (``norm_topk_prob`` false: not renormalised);
every expert is a SwiGLU MLP, ``down(silu(gate(x)) * up(x))``; no shared
expert, no capacity, no token dropped.  Training loss: mean next-token
cross-entropy plus ``router_aux_loss_coef`` x load-balance plus
``router_z_loss_coef`` x z-loss, each computed per layer and summed over the
layers:

    load-balance = E x sum_e f_e P_e     f_e: share of (token, slot) pairs on
                                         expert e; P_e: mean probability of e
    z-loss       = mean over tokens of logsumexp(router logits)^2

No sort, no grouped matmul, no gather: every expert is applied to every
token (one expert at a time, to bound memory) and its output multiplied by
the token's router weight where the expert is among the token's top k and by
zero elsewhere.  Which experts those are is found by counting, per expert,
how many others the token prefers (ties go to the lower index).  float32
under ``default_matmul_precision("highest")``; nothing imported from the
program; norm, RoPE and blockwise attention are ``reference/llama.py``'s.  It
reads the program's parameter pytree (input-major matrices stacked on a
leading layer axis, the experts' on a second), which is layout.

Departures from the published description: the load-balance loss is
normalised so that a uniform router gives 1.0 (ISSUE 26's definition;
``transformers``' ``load_balancing_loss_func`` counts f_e per slot, k times
this, and pools the layers' tokens before the product where this sums the
layers' losses).  Nothing else.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.llama import _attention, _rmsnorm, _rope


def _chosen(probs, k):
    """probs: (T, E) -> bool (T, E): is e among the token's k largest?"""
    E = probs.shape[-1]
    mine, other = probs[:, :, None], probs[:, None, :]
    index = jnp.arange(E)
    ahead = (other > mine) | ((other == mine)
                              & (index[None, None, :] < index[None, :, None]))
    return jnp.sum(ahead, axis=-1) < k


def _moe(h, w, cfg):
    """h: (T, D) -> (y (T, D), load-balance, z, chosen (T, E))."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logits = h @ w["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    chosen = _chosen(probs, k)
    weights = jnp.where(chosen, probs, 0.0)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    def expert(y, e):
        w_gate, w_up, w_down, weight = e
        out = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
        return y + weight[:, None] * out, None

    y, _ = lax.scan(expert, jnp.zeros_like(h),
                    (w["w_gate"], w["w_up"], w["w_down"], weights.T))
    f = jnp.mean(chosen.astype(jnp.float32), axis=0) / k
    balance = E * jnp.sum(f * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return y, balance, z, chosen


def run(params, tokens, cfg, q_block=512):
    """-> (logits (B, S, V), load-balance (L,), z (L,), chosen (L, B*S, E))."""
    D, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = D // H
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    B, S = tokens.shape

    def layer(x, w):
        h = _rmsnorm(x, w["attn_norm"], eps)
        q = _rmsnorm(h @ w["wq"], w["q_norm"], eps)
        k = _rmsnorm(h @ w["wk"], w["k_norm"], eps)
        q = _rope(q.reshape(B, S, H, hd), theta)
        k = _rope(k.reshape(B, S, KV, hd), theta)
        v = (h @ w["wv"]).reshape(B, S, KV, hd)
        attn = _attention(q.reshape(B, S, KV, H // KV, hd), k, v, q_block)
        x = x + attn.reshape(B, S, H * hd) @ w["wo"]
        h = _rmsnorm(x, w["mlp_norm"], eps)
        y, balance, z, chosen = _moe(h.reshape(B * S, D), w, cfg)
        return x + y.reshape(B, S, D), (balance, z, chosen)

    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = params["wte"][tokens]
    x, (balance, z, chosen) = lax.scan(layer, x, params["blocks"])
    x = _rmsnorm(x, params["final_norm"], eps)
    return x @ params["lm_head"].T, balance, z, chosen


def loss(params, tokens, targets, cfg, q_block=512):
    with jax.default_matmul_precision("highest"):
        logits, balance, z, _ = run(params, tokens, cfg, q_block)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, targets[..., None],
                                     axis=-1)[..., 0]
        return jnp.mean(lse - picked) \
            + cfg["router_aux_loss_coef"] * jnp.sum(balance) \
            + cfg["router_z_loss_coef"] * jnp.sum(z)
