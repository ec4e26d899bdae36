"""The latent-attention mixer: the layer of a hybrid decoder
(``models/hybrid.py``) whose queries and keys are no projections of the
layer's input but of two normed low-rank latents, as the ``deepseek_v3``
family has it (multi-head latent attention; kind ``L``).  Not a model; the
file is the mixer, its parameters and its sizes, with the interface
``hybrid.KINDS`` asks of a kind.

Per layer, on ``h = norm(x)`` (H heads; a q.k head is ``n`` lanes without
position and then ``r`` rotary ones, a v head ``dv`` lanes):

    c_q            = norm(h wq_a)                the query latent
    [q_nope|q_rot] = c_q wq_b                    a head: n | r
    [c_kv | k_rot] = h wkv_a                     k_rot ONE head for all H
    c_kv           = norm(c_kv)                  the key-value latent
    [k_nope | v]   = c_kv wkv_b                  a head: n | dv
    q_rot, k_rot   = rope(q_rot), rope(k_rot)    ``mla_rope_theta``, and with
                                                 ``mla_rope_interleave`` the
                                                 pairing (2i, 2i + 1)
    q = [q_nope | q_rot],  k = [k_nope | k_rot for every head]
    o = softmax_causal(q k^T / sqrt(n + r)) v    dv wide
    out = x + concat_h(o) wo

With ``mla_rope_yarn`` (a ``layers.Yarn``) the rotary frequencies are its
table and cos and sin take its scale; ``mla_sm_scale`` states the softmax's
scale where it is not ``(n + r)^-1/2`` (the family multiplies it by the
square of YaRN's ``0.1 mscale_all_dim ln(factor) + 1``).  Without either
nothing of that is traced.

:func:`branch` is the mixer without its residual add, ``concat_h(o) wo``
over ``norm(u)``: what a residual of several streams (``models/streams.py``)
runs between its read and its write.

The attention runs **unabsorbed**: k and v are made a head, as training has
them; folding ``wkv_b`` into the query and caching the latent is a serving
form (ROADMAP B).  The kernel takes v at its own head dimension
(``ops/attention.py``).

The projections multiply in the compute dtype with float32 accumulation;
the two latent norms are float32 passes that round once.  The rotary pass
runs over q's whole head at once (``layers.rope`` with ``rotary``: the
lanes without position pass at an angle of zero) and over the one rotary
key by itself.

Scopes: the whole mixer is ``attn``; inside it ``latent`` holds everything
between the pre-norm and the kernel (the two down-projections, the latent
norms, the two up-projections, the rotary passes, assembling k), and the
kernel call is ``attn_kernel`` as everywhere.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.layers import dense, rmsnorm, rope, stacked_normal
from ray_tpu.ops import remat
from ray_tpu.ops.attention import causal_attention, dq_partial_bytes


def _dims(config):
    """(H, q.k head without position, its rotary lanes, v head)."""
    return (config.mla_heads, config.mla_nope_dim, config.mla_rope_dim,
            config.mla_v_dim)


def init_params(config, key, n: int, out_std: float) -> Dict[str, Any]:
    """``n`` mixers stacked on a leading axis.  Matrices normal(0.02),
    ``wo`` normal(``out_std``), the norms ones."""
    D, Lq, Lkv = config.d_model, config.mla_q_latent, config.mla_kv_latent
    H, nope, rot, dv = _dims(config)
    ks = jax.random.split(key, 5)
    norm = partial(stacked_normal, n)

    return {
        "attn_norm": jnp.ones((n, D)),
        "wq_a": norm(ks[0], (D, Lq)), "q_norm": jnp.ones((n, Lq)),
        "wq_b": norm(ks[1], (Lq, H * (nope + rot))),
        "wkv_a": norm(ks[2], (D, Lkv + rot)), "kv_norm": jnp.ones((n, Lkv)),
        "wkv_b": norm(ks[3], (Lkv, H * (nope + dv))),
        "wo": norm(ks[4], (H * dv, D), out_std),
    }


def logical_axes(config) -> Dict[str, Any]:
    """Of the stacked leaves: the down-projections cut as any matrix that
    reads the layer's input (`embed` over `fsdp`), the up-projections by
    their heads (over `tensor`), the latents whole."""
    L = "layers"
    return {"attn_norm": (L, "norm"), "wq_a": (L, "embed", None),
            "q_norm": (L, "norm"), "wq_b": (L, None, "heads"),
            "wkv_a": (L, "embed", None), "kv_norm": (L, "norm"),
            "wkv_b": (L, None, "heads"), "wo": (L, "heads", "embed")}


def matmul_params(config, routed: float) -> int:
    """The matrix entries of one mixer that a position meets."""
    D, Lq, Lkv = config.d_model, config.mla_q_latent, config.mla_kv_latent
    H, nope, rot, dv = _dims(config)
    return D * Lq + Lq * H * (nope + rot) + D * (Lkv + rot) \
        + Lkv * H * (nope + dv) + H * dv * D


def num_params(config) -> int:
    """Of one mixer, its pre-norm and the two latent norms included."""
    return matmul_params(config, 0) + config.d_model + config.mla_q_latent \
        + config.mla_kv_latent


def mixer_flops(config, seq_len: int) -> float:
    """Forward FLOPs a position beside the matrices: QK^T over the q.k head
    and PV over the v head, causal."""
    H, nope, rot, dv = _dims(config)
    return 1.0 * H * (nope + rot + dv) * seq_len


def layer_bytes(config, tokens: int, seq_len: int, tensor: int,
                itemsize: int):
    """For ``hybrid._layer_sizes``, a chip's bytes of one layer over
    ``tokens`` positions with the heads cut ``tensor`` ways: (its working
    set: q and k with their cotangents, v and the output with theirs,
    ``wkv_b``'s product before it is cut into k and v, and the dq partials
    of the splash call's fused backward, ``attention.dq_partial_bytes``;
    what it keeps for the
    backward beside its input: the kernel's output and log-sum-exp; the
    rungs it names: the two normed latents and the rotary key,
    ``remat.LATENTS``, which spare the two products down and the norms'
    passes, and q, k and v, which spare the two products up from the
    latents and the rotary passes over q and k)."""
    H, nope, rot, dv = _dims(config)
    heads = H // tensor
    qk, v = heads * (nope + rot), heads * dv
    Lq, Lkv, D = config.mla_q_latent, config.mla_kv_latent, config.d_model
    latents = Lq + Lkv + rot
    return (tokens * (4 * qk + 4 * v + heads * (nope + dv)) * itemsize
            + dq_partial_bytes(tokens, seq_len, heads, nope + rot, itemsize,
                               config.attn_impl),
            tokens * (v * itemsize + heads * 4),
            {remat.LATENTS: (tokens * latents * itemsize, remat.spared(
                flops=2.0 * tokens * D * latents,
                moved=tokens * (2 * latents * (4 + itemsize)))),
             remat.QKV: (tokens * (2 * qk + v) * itemsize, remat.spared(
                 flops=2.0 * tokens * (Lq * qk + Lkv * heads * (nope + dv)),
                 moved=tokens * 2 * 2 * qk * itemsize))})


def first_call_facts(config, rows: int, seq_len: int) -> Dict[str, Any]:
    H, nope, rot, dv = _dims(config)
    return {"attn_positions": seq_len, "mla_heads": H,
            "mla_qk_head_dim": nope + rot, "mla_v_head_dim": dv,
            "mla_latents": (config.mla_q_latent, config.mla_kv_latent)}


def _wo_of(x, blk, config, axes):
    """``wo(...)`` over ``norm(x)``, inside the caller's ``attn`` scope."""
    dt = config.dtype
    B, S, _ = x.shape
    H, nope, rot, dv = _dims(config)
    Lkv = config.mla_kv_latent
    turn = partial(rope, theta=config.mla_rope_theta,
                   interleave=config.mla_rope_interleave)
    yarn = config.mla_rope_yarn
    if yarn is not None:
        turn = partial(turn, scale=yarn.scale,
                       inv_freq=yarn.inv_freq(rot, config.mla_rope_theta))
    h = rmsnorm(x, blk["attn_norm"], config.rms_eps).astype(dt)
    with jax.named_scope("latent"):
        c_q = checkpoint_name(
            rmsnorm(dense(h, blk, "wq_a", axes, dt), blk["q_norm"],
                    config.rms_eps).astype(dt), remat.LATENTS)
        q = (c_q @ blk["wq_b"].astype(dt)).reshape(B, S, H, nope + rot)
        q = turn(q, rotary=rot)
        down = dense(h, blk, "wkv_a", axes, dt)
        c_kv = checkpoint_name(
            rmsnorm(down[..., :Lkv], blk["kv_norm"],
                    config.rms_eps).astype(dt), remat.LATENTS)
        k_rot = checkpoint_name(turn(down[..., Lkv:].reshape(B, S, 1, rot)),
                                remat.LATENTS)
        up = (c_kv @ blk["wkv_b"].astype(dt)).reshape(B, S, H, nope + dv)
        k = jnp.concatenate(
            [up[..., :nope], jnp.broadcast_to(k_rot, (B, S, H, rot))],
            axis=-1)
        v = up[..., nope:]
    q, k, v = (checkpoint_name(a, remat.QKV) for a in (q, k, v))
    attn = causal_attention(q, k, v, config.attn_impl, config.mla_sm_scale)
    attn = attn.astype(dt).reshape(B, S, H * dv)
    return dense(attn, blk, "wo", axes, dt)


def mixer(x, blk, config, axes):
    """``x + wo(...)``: the layer.  x: (B, S, D) in the compute dtype;
    ``blk`` one layer of :func:`init_params`; ``axes`` of its stack."""
    with jax.named_scope("attn"):
        return x + _wo_of(x, blk, config, axes)


def layer(config, axes, index: int):
    """Layer ``index`` of the kind as (x, its row of the stack) -> (x,
    None)."""
    return lambda x, blk: (mixer(x, blk, config, axes), None)


def branch(config, axes, index: int):
    """Layer ``index`` without its residual add, as (u, its row of the
    stack) -> (``wo(...)`` over ``norm(u)``, None)."""
    def wo_of(u, blk):
        with jax.named_scope("attn"):
            return _wo_of(u, blk, config, axes), None

    return wo_of
