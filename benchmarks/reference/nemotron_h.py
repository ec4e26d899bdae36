"""Plain reference for NVIDIA Nemotron-3-Nano (``nemotron_h``): a stack in
which every layer is ``x + mixer(norm(x))``, the mixer one of three in the
order ``hybrid_override_pattern`` spells out, for one chip's share of the
routed experts and of the vocabulary.  Written from the ``nemotron_h``
modelling code's equations; ``norm`` is RMSNorm with a weight at ``norm_eps``.

``M`` (Mamba-2; H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``,
G = ``n_groups``, N = ``ssm_state_size``, K = ``conv_kernel``):

    [z | xBC | dt] = u @ in_proj           widths HP | HP + 2GN | H
    xBC = silu(conv(xBC) + conv_b)         causal depthwise, K shifted
                                           multiply-adds, zeros before the row
    [x | B | C] = xBC                      head h reads group h // (H / G)
    delta_t = softplus(dt_t + dt_bias);  A = -exp(A_log);  a_t = exp(delta_t A)
    h_t = a_t h_{t-1} + delta_t x_t B_t^T  (P x N a head; h_0 = 0)
    y_t = h_t C_t + D x_t
    y = norm_g(y * silu(z))                RMSNorm over each of the G groups
                                           of HP / G channels
                                           (``layer_norm_epsilon``), one weight
    out = y @ out_proj

The recurrence is computed **position by position** (``lax.scan`` over t):
no chunk, no cumulative sum, no decay matrix.

``*``: q, k, v, o without bias, ``num_attention_heads`` query and
``num_key_value_heads`` key heads of ``head_dim``, scale ``head_dim^-0.5``,
causal softmax one block of queries at a time, **no rotary embedding and no
QK-norm**.

``E``: ``s = sigmoid(u @ router)`` over all ``n_routed_experts_published``
outputs; the ``num_experts_per_tok`` experts are the largest of ``s + b``
(ties to the lower id), b the layer's selection bias; the weights are s at
those experts without b, divided by their sum (``norm_topk_prob``), times
``routed_scaling_factor``; expert e is ``relu(u @ w_up_e)^2 @ w_down_e``; the
layer's output is the weighted sum over the chosen experts *held here*
(``experts_held``: what the absent ones would add is left out) plus the
shared expert ``relu(u @ shared_up)^2 @ shared_down``.  Dense: every held
expert is applied to every position and weighted by zero where the position
did not choose it.  b is no parameter: ``default_rng([router_bias_seed,
layer]).standard_normal(outputs) * router_bias_std`` in float32 (numpy),
layer counting the ``E`` layers from 0; the configuration's ``assumed`` has
why.

After the last layer ``norm_f`` and an untied head; the loss is the mean
next-token cross-entropy.  No auxiliary loss.

float32 under ``default_matmul_precision("highest")``; nothing imported from
the program; it reads the program's parameter pytree (matrices input-major,
the layers of a kind stacked on a leading axis under ``ssm``, ``attn``,
``experts``), which is layout.  Each layer is recomputed in the backward
(``jax.checkpoint``), as in ``reference/llama.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference.llama import _attention, _rmsnorm

STACKS = {"M": "ssm", "*": "attn", "E": "experts"}


def conv(x, w, b):
    """x: (B, S, C); w: (K, C), tap K-1 the position itself."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(padded[:, k:k + S] * w[k] for k in range(K))


def recurrence(x, delta, A, B, C, D):
    """x: (b, S, H, P); delta: (b, S, H); A, D: (H,); B, C: (b, S, H, N), a
    head's own.  -> y (b, S, H, P), one position after the other."""
    def step(h, at):
        x_t, delta_t, B_t, C_t = at
        h = jnp.exp(delta_t * A)[..., None, None] * h \
            + (delta_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, C_t) + D[:, None] * x_t

    b, _, H, P = x.shape
    _, y = lax.scan(step, jnp.zeros((b, H, P, B.shape[-1]), x.dtype),
                    tuple(jnp.moveaxis(a, 1, 0) for a in (x, delta, B, C)))
    return jnp.moveaxis(y, 0, 1)


def mamba(u, w, cfg):
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    b, S, _ = u.shape
    inner = H * P
    z, xBC, dt = jnp.split(u @ w["in_proj"], [inner, 2 * inner + 2 * G * N],
                           axis=-1)
    xBC = jax.nn.silu(conv(xBC, w["conv_w"], w["conv_b"]))
    x, B, C = jnp.split(xBC, [inner, inner + G * N], axis=-1)
    B = jnp.repeat(B.reshape(b, S, G, N), H // G, axis=2)
    C = jnp.repeat(C.reshape(b, S, G, N), H // G, axis=2)
    y = recurrence(x.reshape(b, S, H, P), jax.nn.softplus(dt + w["dt_bias"]),
                   -jnp.exp(w["A_log"]), B, C, w["D"])
    gated = (y.reshape(b, S, inner) * jax.nn.silu(z)).reshape(b, S, G, -1)
    normed = gated * lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True)
                               + cfg["layer_norm_epsilon"])
    return (normed.reshape(b, S, inner) * w["gate_norm"]) @ w["out_proj"]


def attention(u, w, cfg, q_block):
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    b, S, _ = u.shape
    q = (u @ w["wq"]).reshape(b, S, KV, H // KV, hd)
    k = (u @ w["wk"]).reshape(b, S, KV, hd)
    v = (u @ w["wv"]).reshape(b, S, KV, hd)
    return _attention(q, k, v, q_block).reshape(b, S, H * hd) @ w["wo"]


def selection_bias(cfg, layer: int):
    if not cfg.get("router_bias_std"):
        return 0.0
    rng = np.random.default_rng([cfg["router_bias_seed"], layer])
    return (rng.standard_normal(cfg["n_routed_experts_published"])
            * cfg["router_bias_std"]).astype(np.float32)


def chosen(ranked_by, k):
    """ranked_by: (T, E) -> bool (T, E): is e among the token's k largest?
    By counting how many others the token prefers (ties to the lower id), a
    block of tokens at a time."""
    index = jnp.arange(ranked_by.shape[-1])

    def block(p):
        mine, other = p[:, :, None], p[:, None, :]
        ahead = (other > mine) | ((other == mine) & (
            index[None, None, :] < index[None, :, None]))
        return jnp.sum(ahead, axis=-1) < k

    step = min(ranked_by.shape[0], 2048)
    return jnp.concatenate([block(ranked_by[i:i + step]) for i in
                            range(0, ranked_by.shape[0], step)], axis=0)


def experts(u, w, cfg, layer: int):
    """u: (T, D) -> the held experts' part of the routed sum plus the shared
    expert, (T, D)."""
    first, stop = cfg["experts_held"]
    scores = jax.nn.sigmoid(u @ w["router"])
    picked = chosen(scores + selection_bias(cfg, layer),
                    cfg["num_experts_per_tok"])
    weights = jnp.where(picked, scores, 0.0)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * cfg["routed_scaling_factor"]

    def expert(y, e):
        w_up, w_down, weight = e
        return y + weight[:, None] * (
            jnp.square(jax.nn.relu(u @ w_up)) @ w_down), None

    y, _ = lax.scan(expert, jnp.zeros_like(u),
                    (w["w_up"], w["w_down"], weights.T[first:stop]))
    return y + jnp.square(jax.nn.relu(u @ w["shared_up"])) @ w["shared_down"]


def logits(params, tokens, cfg, q_block=512):
    eps = cfg["norm_eps"]
    b, S = tokens.shape
    mixers = {
        "M": lambda x, w, i: mamba(_rmsnorm(x, w["ssm_norm"], eps), w, cfg),
        "*": lambda x, w, i: attention(_rmsnorm(x, w["attn_norm"], eps), w,
                                       cfg, q_block),
        "E": lambda x, w, i: experts(
            _rmsnorm(x, w["mlp_norm"], eps).reshape(b * S, -1), w, cfg,
            i).reshape(x.shape),
    }
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = params["wte"][tokens]
    seen = dict.fromkeys(STACKS, 0)
    for kind in cfg["hybrid_override_pattern"]:
        i, seen[kind] = seen[kind], seen[kind] + 1
        w = jax.tree.map(lambda a: a[i], params[STACKS[kind]])
        x = jax.checkpoint(
            lambda x, w, kind=kind, i=i: x + mixers[kind](x, w, i))(x, w)
    return _rmsnorm(x, params["final_norm"], eps) @ params["lm_head"].T


def loss(params, tokens, targets, cfg, q_block=512):
    with jax.default_matmul_precision("highest"):
        out = logits(params, tokens, cfg, q_block)
        lse = jax.nn.logsumexp(out, axis=-1)
        picked = jnp.take_along_axis(out, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)
