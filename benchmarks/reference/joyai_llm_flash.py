"""Plain reference for JD's JoyAI-LLM-Flash (``joyai_llm_flash``; the layer
equations are the ``deepseek_v3`` family's, whose key names its published
``config.json`` follows): multi-head latent attention on every layer, a dense
SwiGLU MLP on the first ``first_k_dense_replace`` layers and a mixture of
experts on the others, and one multi-token-prediction module, for one chip's
share of the routed experts and of the vocabulary.  Every layer is
``x = x + attention(norm(x)); x = x + mlp(norm(x))``; ``norm`` is RMSNorm with
a weight at ``rms_norm_eps``; no bias anywhere.

**Latent attention** (H = ``num_attention_heads``; a q.k head is
``qk_nope_head_dim`` lanes without position and ``qk_rope_head_dim`` rotary
ones, a v head ``v_head_dim``; h the normed input):

    c_q            = norm(h wq_a)               ``q_lora_rank`` wide
    [q_nope|q_rot] = c_q wq_b                   a head
    [c_kv | k_rot] = h wkv_a                    ``kv_lora_rank`` | ONE rotary
                                                key for all heads
    c_kv           = norm(c_kv)
    [k_nope | v]   = c_kv wkv_b                 a head
    q_rot, k_rot   = rope(q_rot), rope(k_rot)   ``rope_theta``, no scaling,
                                                the pairs lanes (2i, 2i + 1)
                                                (``rope_interleave``)
    q = [q_nope | q_rot],  k = [k_nope | k_rot for every head]
    o = softmax_causal(q k^T / sqrt(qk_head_dim)) v
    out = concat_h(o) wo

unabsorbed (k and v made a head), one block of queries at a time.

**Experts**: ``s = sigmoid(u router)`` over all ``n_routed_experts_published``
outputs; the ``num_experts_per_tok`` largest of ``s + b`` (ties to the lower
id; ``n_group`` 1: no groups) are the experts, b the layer's selection bias,
which picks and does not weigh; their weights s divided by their sum
(``norm_topk_prob``) times ``routed_scaling_factor``; expert e is
``(silu(u w_gate_e) * (u w_up_e)) w_down_e``; the layer's output is the
weighted sum over the chosen experts *held here* (``experts_held``: what the
absent ones would add is left out) plus the shared expert of the same make.
Dense: every held expert is applied to every position and weighted by zero
where the position did not choose it.  b is no parameter:
``default_rng([router_bias_seed, layer]).standard_normal(outputs) *
router_bias_std`` in float32 (numpy), layer counting the expert layers, the
prediction module's last.  No auxiliary loss.

**The prediction module** (``num_nextn_predict_layers`` 1), over the last
layer's output x_L *before* the final norm and the row's targets (position
i's is t_{i+1}):

    z_i  = [norm(wte[t_{i+1}]) ; norm(x_L,i)] w_eh        the embedding first
    z'   = one more layer (latent attention, then experts) on z, causal
    loss_mtp = mean over i < S - 1 of CE(lm_head(norm(z'_i)), t_{i+2})

with the same ``wte`` and ``lm_head``.  The loss is ``CE_main +
mtp_loss_weight x loss_mtp``.

float32 under ``default_matmul_precision("highest")``; nothing imported from
the program; it reads the program's parameter pytree (matrices input-major,
the layers of a kind stacked on a leading axis under ``mla``, ``dense``,
``experts``, the module's rows after the layers', its own leaves under
``mtp``), which is layout.  Each half of a layer is recomputed in the
backward (``jax.checkpoint``), as in ``reference/llama.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference.llama import _attention, _rmsnorm
from benchmarks.reference.solar_open2 import chosen


def rope(x, theta):
    """x: (b, S, heads, r); lanes (2i, 2i + 1) are a pair, rotated by the
    position's angle at frequency ``theta^(-2i / r)``."""
    S, r = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (f(angles)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(h, w, cfg, q_block):
    H, nope, rot, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    eps, theta, rank = (cfg["rms_norm_eps"], cfg["rope_theta"],
                        cfg["kv_lora_rank"])
    b, S, _ = h.shape
    c_q = _rmsnorm(h @ w["wq_a"], w["q_norm"], eps)
    q = (c_q @ w["wq_b"]).reshape(b, S, H, nope + rot)
    down = h @ w["wkv_a"]
    c_kv = _rmsnorm(down[..., :rank], w["kv_norm"], eps)
    k_rot = rope(down[..., rank:].reshape(b, S, 1, rot), theta)
    up = (c_kv @ w["wkv_b"]).reshape(b, S, H, nope + dv)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_rot, (b, S, H, rot))], axis=-1)
    # every head its own key and value: groups of one
    o = _attention(q[:, :, :, None], k, up[..., nope:], q_block)
    return o.reshape(b, S, H * dv) @ w["wo"]


def selection_bias(cfg, layer: int):
    if not cfg.get("router_bias_std"):
        return 0.0
    rng = np.random.default_rng([cfg["router_bias_seed"], layer])
    return (rng.standard_normal(cfg["n_routed_experts_published"])
            * cfg["router_bias_std"]).astype(np.float32)


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def experts(u, w, cfg, layer: int):
    """u: (T, D) -> the held experts' part of the routed sum plus the shared
    expert, (T, D)."""
    first, stop = cfg["experts_held"]
    scores = jax.nn.sigmoid(u @ w["router"])
    weights = jnp.where(chosen(scores + selection_bias(cfg, layer),
                               cfg["num_experts_per_tok"]), scores, 0.0)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * cfg["routed_scaling_factor"]

    def expert(y, e):
        w_gate, w_up, w_down, weight = e
        return y + weight[:, None] * swiglu(u, w_gate, w_up, w_down), None

    y, _ = lax.scan(expert, jnp.zeros_like(u),
                    (w["w_gate"], w["w_up"], w["w_down"],
                     weights.T[first:stop]))
    return y + swiglu(u, w["shared_gate"], w["shared_up"], w["shared_down"])


def cross_entropy(out, targets):
    """out: (b, S, V) logits -> (b, S)."""
    lse = jax.nn.logsumexp(out, axis=-1)
    return lse - jnp.take_along_axis(out, targets[..., None], axis=-1)[..., 0]


def losses(params, tokens, targets, cfg, q_block=512):
    """-> (CE_main, loss_mtp)."""
    eps = cfg["rms_norm_eps"]
    b, S = tokens.shape
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    def row(stack, i):
        return jax.tree.map(lambda a: a[i], params[stack])

    @jax.checkpoint
    def mixer(x, w):
        return x + attention(_rmsnorm(x, w["attn_norm"], eps), w, cfg,
                             q_block)

    @jax.checkpoint
    def dense(x, w):
        return x + swiglu(_rmsnorm(x, w["mlp_norm"], eps), w["w_gate"],
                          w["w_up"], w["w_down"])

    def moe(x, w, layer):
        return jax.checkpoint(lambda x, w: x + experts(
            _rmsnorm(x, w["mlp_norm"], eps).reshape(b * S, -1), w, cfg,
            layer).reshape(x.shape))(x, w)

    leading = cfg["first_k_dense_replace"]
    x = params["wte"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = mixer(x, row("mla", i))
        x = dense(x, row("dense", i)) if i < leading \
            else moe(x, row("experts", i - leading), i - leading)
    head = params["lm_head"].T
    main = jnp.mean(cross_entropy(
        _rmsnorm(x, params["final_norm"], eps) @ head, targets))
    if not cfg["num_nextn_predict_layers"]:
        return main, jnp.zeros(())

    mtp, deep = params["mtp"], cfg["num_hidden_layers"]
    z = jnp.concatenate(
        [_rmsnorm(params["wte"][targets], mtp["embed_norm"], eps),
         _rmsnorm(x, mtp["hidden_norm"], eps)], axis=-1) @ mtp["w_eh"]
    z = moe(mixer(z, row("mla", deep)), row("experts", deep - leading),
            deep - leading)
    ahead = cross_entropy(
        (_rmsnorm(z, mtp["final_norm"], eps) @ head)[:, :-1], targets[:, 1:])
    return main, jnp.mean(ahead)


def loss(params, tokens, targets, cfg, q_block=512):
    with jax.default_matmul_precision("highest"):
        main, ahead = losses(params, tokens, targets, cfg, q_block)
        return main + cfg["mtp_loss_weight"] * ahead
