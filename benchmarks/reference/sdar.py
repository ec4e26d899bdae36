"""Plain reference for SDAR-30B-A3B-Chat trained as a block-diffusion model
(JetLM's ``sdar_moe``: Qwen3-MoE's decoder under SDAR's training recipe), for
one chip's share of its experts.

**The decoder**, per layer: pre-RMSNorm; q, k, v projections without bias to
``num_attention_heads`` / ``num_key_value_heads`` heads of ``head_dim`` (which
is not ``hidden_size / num_attention_heads``); an RMSNorm over each q head
and each k head (one ``head_dim`` vector for q, one for k, shared by the
heads); rotate-half RoPE; grouped-query softmax attention at scale
``1 / sqrt(head_dim)``; output projection; residual; pre-RMSNorm; mixture of
experts; residual.  The router's logits are ``h @ router`` over all
``num_experts_published`` experts, softmax in float32, the
``num_experts_per_tok`` largest probabilities renormalised to sum to one
(``norm_topk_prob``); every expert is ``down(silu(gate(x)) * up(x))``; no
shared expert, no capacity.  Final RMSNorm, untied head.

**The share.**  The parameters hold the experts ``experts_held[0] ..
experts_held[1]`` only (a chip of an expert-parallel job).  The layer's output
is the sum over the *held* experts among a token's chosen ones; what the
absent experts would add is left out.  With every expert held it is the whole
layer.

**Block-diffusion training** of a row ``x0`` of S ids in blocks of
``block_length``: the input is ``[xt ; x0]``, 2S positions, ``xt`` the row
with some ids replaced by ``mask_token_id``.  Position i has block
``(i mod S) // block_length`` and RoPE position ``i mod S``.  A noised query
reads the noised keys of its own block and the clean keys of earlier blocks;
a clean query reads the clean keys of its own and earlier blocks; nothing
else.  Per block, m ~ U{0 .. block_length} positions are masked, a uniformly
random subset; the loss is the mean over blocks with m >= 1 of the block's
mean cross-entropy over its masked positions (the prediction at a masked
position of ``xt`` is for the id it covers: no shift), plus
``router_aux_loss_coef`` x the layers' summed load-balance losses
(E x sum_e f_e P_e over all E experts, 1.0 under a uniform router).  The head
runs on the noised copy only.  ``targets`` is not read.

**The draw** is the configuration's recipe (its ``assumed.noise``), a function
of the row and ``noise_seed``, written out here so that this file and the
program mask the same positions: key ``fold_in(key(noise_seed), sum(row) mod
2^31)`` split in two; m from the first by ``randint``; from the second 32
random bits a position; a position is masked when fewer than m positions of
its block drew fewer bits.

No kernel, no sort, no grouped matmul: a dense mask, attention one block of
queries at a time against every key, every held expert applied to every
position and weighted by zero where the position did not choose it.  float32
under ``default_matmul_precision("highest")``; nothing imported from the
program; it reads the program's parameter pytree (input-major matrices
stacked on a leading layer axis, the held experts' on a second), which is
layout.  Each layer is recomputed in the backward, as in ``reference/llama.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.llama import _rmsnorm


def _rope(x, theta, positions):
    """x: (B, P, heads, head_dim); rotate-half form at ``positions`` (P,)."""
    hd = x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def may_read(q_index, k_index, S, block_length):
    """(Q, K) bool: query at index q of the 2S positions reads key at k."""
    q_clean, k_clean = q_index[:, None] >= S, k_index[None, :] >= S
    q_block = (q_index % S)[:, None] // block_length
    k_block = (k_index % S)[None, :] // block_length
    noised_noised = ~q_clean & ~k_clean & (q_block == k_block)
    noised_clean = ~q_clean & k_clean & (k_block < q_block)
    clean_clean = q_clean & k_clean & (k_block <= q_block)
    return noised_noised | noised_clean | clean_clean


def _attention(q, k, v, S, block_length, q_block):
    """q: (B, 2S, KV, G, hd); k, v: (B, 2S, KV, hd)."""
    P, hd = q.shape[1], q.shape[-1]
    keys = jnp.arange(P)
    out = []
    for start in range(0, P, q_block):
        qb = q[:, start:start + q_block]
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qb, k) / jnp.sqrt(
            jnp.float32(hd))
        mask = may_read(start + jnp.arange(qb.shape[1]), keys, S,
                        block_length)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bkgqs,bskd->bqkgd", probs, v))
    return jnp.concatenate(out, axis=1)


def _chosen(probs, k):
    """probs: (T, E) -> bool (T, E): is e among the token's k largest?  By
    counting how many others the token prefers (ties to the lower index), a
    block of tokens at a time."""
    E = probs.shape[-1]
    index = jnp.arange(E)

    def block(p):
        mine, other = p[:, :, None], p[:, None, :]
        ahead = (other > mine) | ((other == mine) & (
            index[None, None, :] < index[None, :, None]))
        return jnp.sum(ahead, axis=-1) < k

    T = probs.shape[0]
    step = min(T, 2048)
    return jnp.concatenate([block(probs[i:i + step])
                            for i in range(0, T, step)], axis=0)


def moe(h, w, cfg):
    """h: (T, D); ``w`` holds ``router`` (D, E) and the held experts'
    ``w_gate``, ``w_up`` (H, D, F), ``w_down`` (H, F, D).  -> (the held
    experts' part of the layer's output (T, D), load-balance)."""
    E, k = cfg["num_experts_published"], cfg["num_experts_per_tok"]
    first, stop = cfg["experts_held"]
    probs = jax.nn.softmax(h @ w["router"], axis=-1)
    chosen = _chosen(probs, k)
    weights = jnp.where(chosen, probs, 0.0)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    def expert(y, e):
        w_gate, w_up, w_down, weight = e
        out = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
        return y + weight[:, None] * out, None

    y, _ = lax.scan(expert, jnp.zeros_like(h),
                    (w["w_gate"], w["w_up"], w["w_down"],
                     weights.T[first:stop]))
    f = jnp.mean(chosen.astype(jnp.float32), axis=0) / k
    return y, E * jnp.sum(f * jnp.mean(probs, axis=0))


def masked_positions(tokens, cfg):
    """tokens: (B, S) -> (masked (B, S) bool, m (B, S / block_length))."""
    Bk = cfg["block_length"]
    S = tokens.shape[1]
    blocks = S // Bk
    out = []
    for row in tokens:
        total = jnp.sum(row.astype(jnp.uint32)) % jnp.uint32(2 ** 31)
        key = jax.random.fold_in(jax.random.key(cfg["noise_seed"]), total)
        key_m, key_bits = jax.random.split(key)
        m = jax.random.randint(key_m, (blocks,), 0, Bk + 1)
        bits = jax.random.bits(key_bits, (blocks, Bk), jnp.uint32)
        fewer = jnp.sum(bits[:, None, :] < bits[:, :, None], axis=-1)
        out.append(((fewer < m[:, None]).reshape(S), m))
    return jnp.stack([a for a, _ in out]), jnp.stack([b for _, b in out])


def run(params, tokens, cfg, q_block=512):
    """tokens: (B, S) clean rows.  -> (logits of the noised copy (B, S, V),
    load-balance (L,), masked (B, S), m (B, blocks))."""
    D, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd, Bk = cfg["head_dim"], cfg["block_length"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    B, S = tokens.shape
    P = 2 * S
    masked, m = masked_positions(tokens, cfg)
    noised = jnp.where(masked, cfg["mask_token_id"], tokens)
    inputs = jnp.concatenate([noised, tokens], axis=1)
    positions = jnp.arange(P) % S

    def layer(x, w):
        h = _rmsnorm(x, w["attn_norm"], eps)
        q = _rmsnorm((h @ w["wq"]).reshape(B, P, H, hd), w["q_norm"], eps)
        k = _rmsnorm((h @ w["wk"]).reshape(B, P, KV, hd), w["k_norm"], eps)
        q, k = _rope(q, theta, positions), _rope(k, theta, positions)
        v = (h @ w["wv"]).reshape(B, P, KV, hd)
        attn = _attention(q.reshape(B, P, KV, H // KV, hd), k, v, S, Bk,
                          q_block)
        x = x + attn.reshape(B, P, H * hd) @ w["wo"]
        h = _rmsnorm(x, w["mlp_norm"], eps)
        y, balance = moe(h.reshape(B * P, D), w, cfg)
        return x + y.reshape(B, P, D), balance

    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = params["wte"][inputs]
    x, balance = lax.scan(jax.checkpoint(layer), x, params["blocks"])
    x = _rmsnorm(x[:, :S], params["final_norm"], eps)
    return x @ params["lm_head"].T, balance, masked, m


def loss(params, tokens, targets, cfg, q_block=512):
    del targets  # a masked position predicts the id it covers
    with jax.default_matmul_precision("highest"):
        logits, balance, masked, m = run(params, tokens, cfg, q_block)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tokens[..., None],
                                     axis=-1)[..., 0]
        Bk = cfg["block_length"]
        per_position = jnp.where(masked, lse - picked, 0.0)
        per_block = jnp.sum(per_position.reshape(*m.shape, Bk), axis=-1) \
            / jnp.maximum(m, 1)
        ce = jnp.sum(per_block) / jnp.maximum(jnp.sum(m > 0), 1)
        return ce + cfg["router_aux_loss_coef"] * jnp.sum(balance)
