"""The two halves of a pre-norm decoder layer, and what they are made of:
RMSNorm, rotary embedding, attention with its four projections, and the
feed-forward part (a SwiGLU MLP, or ``models/moe.py``'s expert layer).

Not a model: ``models/llama.py`` puts the two halves into one block under a
``lax.scan``; ``models/hybrid.py`` makes each a layer of its own in a stack
whose order the configuration spells out.  No decoder imports another; both
import this file.  A ``config`` here is any object with the fields a half
reads (each function says which); ``axes`` are the logical axes of ``blk``'s
stacked leaves, layer axis first (a decoder's ``logical_axes``), from which
:func:`dense` learns which axis of a weight is `embed`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import moe as _moe
from ray_tpu.ops import grad_ring, remat
from ray_tpu.ops.attention import block_diffusion_attention, causal_attention
from ray_tpu.parallel.mesh import DEFAULT_RULES


def mesh_axes(logical):
    """The mesh axes a parameter with these logical axes is cut over."""
    return tuple(a for name in logical if name is not None
                 for a in DEFAULT_RULES.get(name) or ())


def stacked_normal(n: int, key, shape, std: float = 0.02):
    """``n`` matrices of ``shape`` on a leading axis, normal(``std``)."""
    return jax.random.normal(key, (n, *shape), jnp.float32) * std


def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return x32 * lax.rsqrt(ms + eps) * scale


def _rope_pass(x, theta: float, direction: float, rotary=None,
               interleave: bool = False):
    """x * cos + partner(x) * (-sin | +sin) over (B, S, H, hd), cos and sin
    from float32 angles, products and sum in float32, one rounding to x's
    dtype.  ``direction`` 1.0 rotates each pair by its position's angle,
    -1.0 back.  The pairing is rotate-half, lanes (i, i + hd/2), as the
    Llama family publishes it, or with ``interleave`` lanes (2i, 2i + 1), as
    the ``deepseek_v3`` family does (``rope_interleave``).  With ``rotary``
    only the head's last ``rotary`` lanes rotate, over frequencies of their
    own (a latent-attention head: 128 lanes without position, then 64 with);
    the lanes before them pass at an angle of zero.

    Everything stays hd wide so that the compiler makes it one pass, x read
    once and the result written once in x's dtype.  Slicing the two halves
    (or ``jnp.roll``, which is two slices) gave arrays of 64 lanes padded to
    128 and a float32 copy of x in HBM: three passes forward and three
    backward, 0.6 and 0.95 GB a layer for Mistral's q at 8192 tokens where
    this needs 0.2 and 0.13 (PERF.md, PR 27).  The partners are swapped by a
    product with a 0/1 permutation instead: each output is one input times
    one, so it is exact, and cos, sin and the cast fuse into its output.
    """
    hd = x.shape[-1]
    rot = hd if rotary is None else rotary
    half = rot // 2
    lane = jnp.arange(hd)
    # a lane's place in the rotary part; negative before it.  (Where the
    # whole head rotates nothing is traced for the part: the older models'
    # lowered steps are pinned by their text, tests/test_nemotron_h.py.)
    at = lane if rot == hd else lane - (hd - rot)
    # (S, hd): the two lanes of a pair share a frequency, so an angle
    pair = at // 2 if interleave else at % half
    freqs = 1.0 / (theta ** (pair.astype(jnp.float32) / half))
    if rot != hd:
        freqs = jnp.where(at >= 0, freqs, 0.0)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    first = at % 2 == 0 if interleave else at < half
    sign = jnp.where(first, -direction, direction)

    def partner(of):
        if interleave:
            return of ^ 1  # rot is even, so a pair lies inside the part
        if rot == hd:
            return (of + half) % hd
        return (of - (hd - rot) + half) % rot + (hd - rot)

    cos = jnp.cos(angles)[None, :, None, :]
    sin = (jnp.sin(angles) * sign)[None, :, None, :]
    swap = (lane[:, None] == partner(lane[None, :])).astype(x.dtype)
    swapped = jnp.einsum("bshd,de->bshe", x, swap,
                         preferred_element_type=jnp.float32,
                         precision=lax.Precision.HIGHEST)
    return (x.astype(jnp.float32) * cos + swapped * sin).astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def rope(x, theta: float, rotary=None, interleave: bool = False):
    """Rotary position embedding over (B, S, H, hd), in and out in x's
    dtype: rotate-half form over the whole head by default, over the head's
    last ``rotary`` lanes and in the ``interleave``d pairing where asked
    (:func:`_rope_pass`).  The backward is the same pass with the sine
    negated (a rotation's transpose is the rotation back), not autodiff's
    sum over sliced halves."""
    return _rope_pass(x, theta, 1.0, rotary, interleave)


def _rope_fwd(x, theta, rotary, interleave):
    return _rope_pass(x, theta, 1.0, rotary, interleave), None


def _rope_bwd(theta, rotary, interleave, _, g):
    return (_rope_pass(g, theta, -1.0, rotary, interleave),)


rope.defvjp(_rope_fwd, _rope_bwd)


def dense(a, blk, name: str, axes, dtype):
    """``a @ blk[name]`` in the compute dtype; under `fsdp` the weight's
    gradient is summed while it multiplies (``ops/grad_ring.py``), along
    the axis ``axes`` calls `embed` (the layer axis is scanned or indexed
    away before a half sees ``blk``)."""
    return grad_ring.dense(a, blk[name].astype(dtype),
                           axes[name][1:].index("embed"))


def attention(x, blk, config, axes):
    """The attention half: ``x + wo(attention(q, k, v))`` over
    ``norm(x)``'s projections.  Reads ``dtype``, ``n_head``, ``n_kv_head``,
    ``head_dim``, ``qk_norm``, ``rms_eps``, ``rope_theta`` (``None``: no
    rotary embedding, the positions reach the model some other way),
    ``block_length`` and ``attn_impl``; of ``blk`` ``attn_norm``, ``wq``,
    ``wk``, ``wv``, ``wo`` and with QK-norm ``q_norm``, ``k_norm``.  Where
    ``blk`` holds ``wg`` (D, H x hd), an output gate: ``wo`` reads ``attn *
    sigmoid(norm(x) wg)``, a channel each, made in float32."""
    dt = config.dtype
    B, S, D = x.shape
    H, KV, hd = config.n_head, config.n_kv_head, config.head_dim
    with jax.named_scope("attn"):
        h = rmsnorm(x, blk["attn_norm"], config.rms_eps).astype(dt)
        q = dense(h, blk, "wq", axes, dt)
        k = dense(h, blk, "wk", axes, dt)
        if config.qk_norm == "head":
            q, k = q.reshape(B, S, H, hd), k.reshape(B, S, KV, hd)
        if config.qk_norm:
            q = rmsnorm(q, blk["q_norm"], config.rms_eps).astype(dt)
            k = rmsnorm(k, blk["k_norm"], config.rms_eps).astype(dt)
        v = dense(h, blk, "wv", axes, dt).reshape(B, S, KV, hd)
        if config.rope_theta is None:
            q, k = q.reshape(B, S, H, hd), k.reshape(B, S, KV, hd)
        else:
            # A block-diffusion row is two copies that share positions: each
            # rotates as a row of its own, so a position is its axis index.
            copies = 2 if config.block_length else 1
            q = rope(q.reshape(B * copies, S // copies, H, hd),
                     config.rope_theta).reshape(B, S, H, hd)
            k = rope(k.reshape(B * copies, S // copies, KV, hd),
                     config.rope_theta).reshape(B, S, KV, hd)
        q, k, v = (checkpoint_name(a, remat.QKV) for a in (q, k, v))
        # GQA: k and v go in at KV heads; the splash kernel takes them so,
        # and the dispatcher repeats them for the paths that cannot.
        if config.block_length:
            attn = block_diffusion_attention(q, k, v, config.block_length,
                                             config.attn_impl)
        else:
            attn = causal_attention(q, k, v, config.attn_impl)
        attn = attn.astype(dt).reshape(B, S, H * hd)
        if "wg" in blk:
            gate = jax.nn.sigmoid(
                dense(h, blk, "wg", axes, dt).astype(jnp.float32))
            attn = (attn.astype(jnp.float32) * gate).astype(dt)
        return x + dense(attn, blk, "wo", axes, dt)


def feed_forward(x, blk, config, axes, **expert_layer):
    """The feed-forward half.  -> (x + its output, what the expert layer
    says of itself): the second is ``None`` for a dense MLP; with experts
    (``blk`` holds a ``router``) it is (moe.router_losses' pair, the layer's
    counts: ``moe.moe_mlp``).  Reads ``dtype``, ``rms_eps`` and, with experts,
    ``experts_per_token``, ``norm_topk_prob`` and ``held``; of ``blk``
    ``mlp_norm`` and the SwiGLU's ``w_gate``, ``w_up``, ``w_down``, or what
    ``moe.moe_mlp`` reads, which is also handed ``expert_layer``."""
    dt = config.dtype
    with jax.named_scope("mlp"):
        h = rmsnorm(x, blk["mlp_norm"], config.rms_eps)
        if "router" in blk:
            # the router reads the norm's float32 output, the experts its
            # cast to the compute dtype
            y, router_losses, counts = _moe.moe_mlp(
                h, blk, experts_per_token=config.experts_per_token,
                norm_topk_prob=config.norm_topk_prob, dtype=dt,
                first_held=config.held.start, **expert_layer)
            return x + y, (router_losses, counts)
        h = h.astype(dt)
        gate = checkpoint_name(dense(h, blk, "w_gate", axes, dt),
                               remat.GATE_UP)
        up = checkpoint_name(dense(h, blk, "w_up", axes, dt), remat.GATE_UP)
        act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
        x = x + dense(act.astype(dt), blk, "w_down", axes, dt)
    return x, None
