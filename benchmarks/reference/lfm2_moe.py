"""Plain reference for Liquid AI's LFM2 mixture of experts (``lfm2_moe``;
LFM2-8B-A1B's published ``config.json``): a decoder whose token mixer is a
gated short convolution on three layers in four and rotary GQA on the
fourth (``layer_types``), before a dense SwiGLU MLP on the first
``num_dense_layers`` layers and a mixture of experts after them, for one
chip's share of the routed experts and of the vocabulary.  Every layer is
``x = x + Mixer(norm(x)); x = x + FFN(norm(x))``; ``norm`` is RMSNorm with a
weight at ``norm_eps``; no bias anywhere.

**Gated short convolution** (``conv``; h the normed input, hidden wide; K =
``conv_L_cache`` taps):

    [B | C | u] = h in_proj                 hidden x 3 hidden, cut in three
                                            of hidden in that order
    z   = B * u
    c_t = sum_{j=0..K-1} w_j z_{t-(K-1)+j}  a channel: depthwise, causal,
                                            zeros before the row's first
                                            position, no bias
                                            (``conv_bias`` false), **no
                                            activation**
    y   = (C * c) out_proj                  hidden x hidden

written here as K shifted multiply-adds over a zero-padded copy.

**Attention** (``full_attention``; H = ``num_attention_heads`` query heads
over ``num_key_value_heads`` key heads of ``hidden_size / H`` = 64):

    q, k, v = h wq, h wk, h wv
    q, k    = norm_q(q), norm_k(k)          RMSNorm over each head's lanes,
                                            one learned weight of the head's
                                            width for all heads, at
                                            ``norm_eps``
    q, k    = rotary(q), rotary(k)          the whole head, rotate-half
                                            pairs (i, i + 32), ``inv_freq_i
                                            = rope_theta^(-2i / 64)``
    scores  = q k^T / sqrt(64), causal; softmax and PV in float32
    y       = concat_h(o_h) wo

one block of queries at a time against every key, the mask an explicit
comparison of positions.

**Experts** (layers from ``num_dense_layers`` on): ``s = sigmoid(h router)``
in float32 over all ``num_experts_published`` outputs; the
``num_experts_per_tok`` experts are the largest of ``s + b`` (ties to the
lower id), b the layer's selection bias (``use_expert_bias``); the weights
are s at those experts without b, divided by their sum (``norm_topk_prob``),
times ``routed_scaling_factor``; expert e is ``(silu(h w_gate_e) * (h
w_up_e)) w_down_e``; the layer's output is the weighted sum over the chosen
experts *held here* (``experts_held``: what the absent ones would add is
left out), a loop over the held ids.  No shared expert, no auxiliary loss.
b is no parameter: ``default_rng([router_bias_seed, layer]).standard_normal(
outputs) * router_bias_std`` in float32 (numpy), layer counting the expert
layers run from 0.  **Dense** layers: ``(silu(h w_gate) * (h w_up))
w_down``, ``intermediate_size`` wide.  After the last layer a final norm and
**the embedding as the head** (tied); the loss is the mean next-token
cross-entropy.

**Which layers run**: ``lib/cost_lfm2.py:layers_run`` (a cut counts the
leading dense layers once: layer 0, then the layers from
``num_dense_layers`` on, each with the mixer ``layer_types`` gives its own
index).

**Departures from the published description**, each an assumption the
configuration's ``assumed`` states (the catalog's row gives keys, not
formulas; the choices are the ``lfm2_moe`` family's in ``transformers``):

- the head is tied to the embedding (the row carries no tying key);
- QK-norm a head (the row has no key for it);
- the renormalisation divides by the plain sum of the chosen scores (the
  family adds 1e-6 to it: four sigmoid scores sum to about 2, so the term is
  under float32's rounding of the sum);
- the selection bias is a constant of the configuration (the published
  recipe moves it by the load, outside the gradient);
- only the held experts' part of the routed sum is computed, and the logits
  are over a slice of the vocabulary.

float32 under ``default_matmul_precision("highest")``; nothing imported from
the program; it reads the program's parameter pytree (matrices input-major,
the layers of a kind stacked on a leading axis under ``shortconv``,
``attn``, ``dense``, ``experts``), which is layout.  The layers are walked
one by one, each half recomputed in the backward (``jax.checkpoint``), as in
``reference/llama.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.cost_lfm2 import layers_run
from benchmarks.reference.llama import _attention, _rmsnorm, _rope
from benchmarks.reference.nemotron_h import chosen


def short_conv(h, w):
    """The mixer of a ``conv`` layer on its normed input h (b, S, D)."""
    B, C, u = jnp.split(h @ w["in_proj"], 3, axis=-1)
    z = B * u
    K, S = w["conv_w"].shape[0], h.shape[1]
    padded = jnp.pad(z, ((0, 0), (K - 1, 0), (0, 0)))
    c = sum(padded[:, j:j + S] * w["conv_w"][j] for j in range(K))
    return (C * c) @ w["out_proj"]


def attention(h, w, cfg, q_block: int):
    """The mixer of a ``full_attention`` layer on its normed input."""
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    b, S, _ = h.shape
    q = _rmsnorm((h @ w["wq"]).reshape(b, S, H, hd), w["q_norm"], eps)
    k = _rmsnorm((h @ w["wk"]).reshape(b, S, KV, hd), w["k_norm"], eps)
    v = (h @ w["wv"]).reshape(b, S, KV, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    out = _attention(q.reshape(b, S, KV, H // KV, hd), k, v, q_block)
    return out.reshape(b, S, H * hd) @ w["wo"]


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def selection_bias(cfg, layer: int):
    if not cfg.get("router_bias_std"):
        return 0.0
    rng = np.random.default_rng([cfg["router_bias_seed"], layer])
    return (rng.standard_normal(cfg["num_experts_published"])
            * cfg["router_bias_std"]).astype(np.float32)


def experts(u, w, cfg, layer: int):
    """u: (T, D) -> the held experts' part of the routed sum, (T, D)."""
    first, stop = cfg["experts_held"]
    scores = jax.nn.sigmoid(u @ w["router"])
    picked = chosen(scores + selection_bias(cfg, layer),
                    cfg["num_experts_per_tok"])
    weights = jnp.where(picked, scores, 0.0)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * cfg["routed_scaling_factor"]
    y = jnp.zeros_like(u)
    for held, e in enumerate(range(first, stop)):
        y = y + weights[:, e, None] * swiglu(
            u, w["w_gate"][held], w["w_up"][held], w["w_down"][held])
    return y


def logits(params, tokens, cfg, q_block=512):
    eps = cfg["norm_eps"]
    b, S = tokens.shape
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = params["wte"][tokens]
    seen = {"shortconv": 0, "attn": 0, "dense": 0, "experts": 0}

    def row(stack):
        """The next layer of a kind's stack."""
        w = jax.tree.map(lambda a: a[seen[stack]], params[stack])
        seen[stack] += 1
        return w

    for layer in layers_run(cfg):
        if cfg["layer_types"][layer] == "conv":
            w = row("shortconv")
            x = jax.checkpoint(lambda x, w: x + short_conv(
                _rmsnorm(x, w["conv_norm"], eps), w))(x, w)
        else:
            w = row("attn")
            x = jax.checkpoint(lambda x, w: x + attention(
                _rmsnorm(x, w["attn_norm"], eps), w, cfg, q_block))(x, w)
        if layer < cfg["num_dense_layers"]:
            w = row("dense")
            x = jax.checkpoint(lambda x, w: x + swiglu(
                _rmsnorm(x, w["mlp_norm"], eps), w["w_gate"], w["w_up"],
                w["w_down"]))(x, w)
        else:
            i = seen["experts"]
            w = row("experts")
            x = jax.checkpoint(lambda x, w, i=i: x + experts(
                _rmsnorm(x, w["mlp_norm"], eps).reshape(b * S, -1), w, cfg,
                i).reshape(x.shape))(x, w)
    return _rmsnorm(x, params["final_norm"], eps) @ params["wte"].T


def loss(params, tokens, targets, cfg, q_block=512):
    with jax.default_matmul_precision("highest"):
        out = logits(params, tokens, cfg, q_block)
        lse = jax.nn.logsumexp(out, axis=-1)
        picked = jnp.take_along_axis(out, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)
