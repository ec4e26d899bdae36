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

import contextlib
import functools
import math
from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import moe as _moe
from ray_tpu.ops import grad_ring, remat, rope_kernel
from ray_tpu.ops.attention import (block_diffusion_attention,
                                   causal_attention, scales_q,
                                   window_attention)
from ray_tpu.parallel.mesh import DEFAULT_RULES
from ray_tpu.util import first_call


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


@dataclass(frozen=True)
class Yarn:
    """YaRN's settings as a configuration publishes them (``rope_type``
    ``yarn``): the rotary frequencies of a model trained at ``original``
    positions, stretched by ``factor`` where a lane turns fewer than
    ``beta_slow`` times over those positions, left alone where it turns more
    than ``beta_fast`` times, and mixed linearly between; cos and sin are
    multiplied by ``attention_factor`` (``None``: ``0.1 ln(factor) + 1``)."""

    factor: float
    original: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    @property
    def scale(self) -> float:
        return 0.1 * math.log(self.factor) + 1.0 \
            if self.attention_factor is None else self.attention_factor

    @functools.cache
    def inv_freq(self, dim: int, theta: float) -> Tuple[float, ...]:
        """The ``dim // 2`` inverse frequencies of ``dim`` rotary lanes, as
        ``transformers``' ``_compute_yarn_parameters`` makes them, in
        float32 and once: a table, no code in the pass."""
        f32 = np.float32
        at = np.arange(0, dim, 2, dtype=f32) / f32(dim)
        extra = f32(1.0) / np.power(f32(theta), at)
        inter = extra / f32(self.factor)

        def turns(rotations: float) -> float:
            """The lane pair that turns so often over the original row."""
            return dim * math.log(self.original / (rotations * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(turns(self.beta_fast)), 0)
        high = min(math.ceil(turns(self.beta_slow)), dim - 1)
        ramp = np.clip((np.arange(dim // 2, dtype=f32) - low)
                       / f32(max(high - low, 0.001)), 0, 1).astype(f32)
        return tuple(float(f) for f in inter * ramp + extra * (1 - ramp))


def _rope_tables(positions: int, hd: int, theta: float, direction: float,
                 rotary=None, interleave: bool = False, first: bool = False,
                 inv_freq=None, scale: float = 1.0):
    """-> (cos, sin, lane, partner): the float32 tables of a rotary pass over
    ``positions`` positions of ``hd`` lanes, (1, positions, 1, hd) each, the
    sine signed for the pair's two lanes and by ``direction`` (1.0 rotates
    each pair by its position's angle, -1.0 back); the lanes' indices and the
    function from a lane to its partner's.  The pairing is rotate-half, lanes
    (i, i + hd/2), as the Llama family publishes it, or with ``interleave``
    lanes (2i, 2i + 1), as the ``deepseek_v3`` family does
    (``rope_interleave``).  With ``rotary`` only the head's last ``rotary``
    lanes rotate, over frequencies of their own (a latent-attention head: 128
    lanes without position, then 64 with), or with ``first`` its first
    (``partial_rotary_factor``, as ``transformers`` cuts a head); the other
    lanes pass at an angle of zero (cos 1, sin 0).  ``inv_freq``: the rotary
    part's inverse frequencies a pair, as a tuple, in the place of ``theta **
    (-2i / rotary)`` (:class:`Yarn`'s); ``scale`` multiplies cos and sin on
    the lanes that rotate and no other."""
    rot = hd if rotary is None else rotary
    half = rot // 2
    before = 0 if first else hd - rot  # the lanes ahead of the rotary part
    lane = jnp.arange(hd)
    # a lane's place in the rotary part; outside [0, rot) beside it.  (Where
    # the whole head rotates nothing is traced for the part: the older
    # models' lowered steps are pinned by their text,
    # tests/test_nemotron_h.py.)
    at = lane if before == 0 else lane - before
    # (S, hd): the two lanes of a pair share a frequency, so an angle
    pair = at // 2 if interleave else at % half
    if inv_freq is None:
        freqs = 1.0 / (theta ** (pair.astype(jnp.float32) / half))
    else:
        freqs = jnp.asarray(np.asarray(inv_freq, np.float32))[pair]
    if rot != hd:
        inside = at < rot if first else at >= 0
        freqs = jnp.where(inside, freqs, 0.0)
    angles = jnp.arange(positions, dtype=jnp.float32)[:, None] * freqs[None, :]
    first_of_pair = at % 2 == 0 if interleave else at < half
    sign = jnp.where(first_of_pair, -direction, direction)

    def partner(of):
        if interleave:
            return of ^ 1  # rot is even, so a pair lies inside the part
        if rot == hd:
            return (of + half) % hd
        if first:
            return jnp.where(of < rot, (of + half) % rot, of)
        return (of - before + half) % rot + before

    cos = jnp.cos(angles)[None, :, None, :]
    sin = (jnp.sin(angles) * sign)[None, :, None, :]
    if scale != 1.0:
        # an angle of zero is cos 1, sin 0: only cos needs telling apart
        cos = cos * scale if rot == hd else jnp.where(inside, cos * scale, cos)
        sin = sin * scale
    return cos, sin, lane, partner


def _rope_pass(x, theta: float, direction: float, *how):
    """x * cos + partner(x) * sin over (B, S, H, hd) by :func:`_rope_tables`'
    tables (``how``: its arguments from ``rotary`` on), products and sum in
    float32, one rounding to x's dtype: the form for the shapes and places
    ``ops/rope_kernel.py`` does not take.

    Everything stays hd wide so that the compiler makes it one pass, x read
    once and the result written once in x's dtype.  Slicing the two halves
    (or ``jnp.roll``, which is two slices) gave arrays of 64 lanes padded to
    128 and a float32 copy of x in HBM: three passes forward and three
    backward, 0.6 and 0.95 GB a layer for Mistral's q at 8192 tokens where
    this needs 0.2 and 0.13 (PERF.md, PR 27).  The partners are swapped by a
    product with a 0/1 permutation instead: each output is one input times
    one, so it is exact, and cos, sin and the cast fuse into its output.
    What that costs (PERF.md, PR 53: the pass alone at 72 + 8 heads of 8192
    positions): 3.56 ms forward, 8.7 times its bytes' time, and 1.69
    backward; HIGHEST costs nothing where x is bf16 (3.563 without: one pass
    of the matrix unit either way); the forward's two layout copies of x
    inside the fusion about half, the K = N = 128 product the rest.
    """
    cos, sin, lane, partner = _rope_tables(x.shape[1], x.shape[-1], theta,
                                           direction, *how)
    swap = (lane[:, None] == partner(lane[None, :])).astype(x.dtype)
    swapped = jnp.einsum("bshd,de->bshe", x, swap,
                         preferred_element_type=jnp.float32,
                         precision=lax.Precision.HIGHEST)
    return (x.astype(jnp.float32) * cos + swapped * sin).astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _rope_product(x, theta: float, rotary=None, interleave: bool = False,
                  first: bool = False, inv_freq=None, scale: float = 1.0):
    """:func:`rope` of one array by :func:`_rope_pass`.  The backward is the
    same pass with the sine negated (a rotation's transpose is the rotation
    back, and a scale stays a scale), not autodiff's sum over sliced
    halves."""
    return _rope_pass(x, theta, 1.0, rotary, interleave, first, inv_freq,
                      scale)


def _rope_fwd(x, *how):
    return _rope_pass(x, how[0], 1.0, *how[1:]), None


def _rope_bwd(*how_and_g):
    *how, _, g = how_and_g
    return (_rope_pass(g, how[0], -1.0, *how[1:]),)


_rope_product.defvjp(_rope_fwd, _rope_bwd)


def _rope_rolled(xs, direction: float, theta: float, rotary, first: bool,
                 inv_freq, scale: float, copies: int, scales):
    """``xs`` rotated, and scaled where ``scales`` has a factor, by one call
    of ``ops/rope_kernel.py`` over :func:`_rope_tables`' tables of a copy's
    positions."""
    S, hd = xs[0].shape[1], xs[0].shape[-1]
    cos, sin, _, _ = _rope_tables(S // copies, hd, theta, direction, rotary,
                                  False, first, inv_freq, scale)
    rot = hd if rotary is None else rotary
    return rope_kernel.rotate(
        cos.reshape(-1, hd), sin.reshape(-1, hd), xs, half=rot // 2,
        before=0 if first else hd - rot, backward=direction < 0,
        scales=scales)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7))
def _rope_by_kernel(xs, theta: float, rotary, first: bool, inv_freq,
                    scale: float, copies: int, scales):
    """:func:`rope` of a tuple of arrays by the kernel; the backward is the
    kernel with the sine negated, and nothing is saved."""
    return _rope_rolled(xs, 1.0, theta, rotary, first, inv_freq, scale,
                        copies, scales)


def _rope_by_kernel_fwd(xs, *how):
    return _rope_rolled(xs, 1.0, *how), None


def _rope_by_kernel_bwd(*how_and_gs):
    *how, _, gs = how_and_gs
    return (_rope_rolled(tuple(gs), -1.0, *how),)


_rope_by_kernel.defvjp(_rope_by_kernel_fwd, _rope_by_kernel_bwd)


def _rope_takes_kernel(shapes, interleave: bool, copies: int) -> bool:
    """Whether a rotary call over arrays of these (B, S, H, hd) shapes is
    the kernel's, here and now (``ops/rope_kernel.py:path``)."""
    return rope_kernel.path(shapes, interleave, copies,
                            jax.sharding.get_abstract_mesh()) == "kernel"


def rope(x, theta: float, rotary=None, interleave: bool = False,
         first: bool = False, inv_freq=None, scale: float = 1.0,
         copies: int = 1, hd: Optional[int] = None, scales=None):
    """Rotary position embedding over (B, S, H, hd), in and out in x's
    dtype: rotate-half form over the whole head by default, over the head's
    last (or, with ``first``, first) ``rotary`` lanes, in the
    ``interleave``d pairing, over a table of inverse frequencies and with
    cos and sin scaled where asked (:func:`_rope_tables`).  ``x`` may be a
    tuple of arrays that share B, S, hd and a dtype (a layer's q and k) and
    comes back as one; with ``hd`` an array may come as a projection writes
    it, (B, S, H x hd), and goes back (B, S, H, hd).  A row of ``copies``
    copies back to back restarts its positions at each.  ``scales``: a
    factor an array of ``x`` (None: none) that multiplies the rotated array
    in its dtype, ``rope(a) * factor``: the scale an attention kernel wants
    on q (``ops.attention.scales_q``), which the kernel form applies on its
    way out and XLA fuses into the product form.

    One algorithm in two forms, chosen by what the call can observe
    (``ops/rope_kernel.py:path``: the lane width, the pairing, the backend
    and the mesh), with no argument, configuration field or environment
    variable to pick one: a Mosaic kernel that makes the partner lanes by a
    lane roll, one call for all of ``x``, or :func:`_rope_pass`'s product an
    array.  The first-call record says which ran (``rope_kernel``) and how
    many calls a step traces (``rope_calls``)."""
    xs = x if isinstance(x, tuple) else (x,)
    B, S = xs[0].shape[:2]
    hd = hd or xs[0].shape[-1]
    shapes = [(B, S, a.size // (B * S * hd), hd) for a in xs]
    kernel = _rope_takes_kernel(shapes, interleave, copies)
    first_call.note(rope_kernel=kernel)
    first_call.count("rope_calls")
    if kernel:
        out = _rope_by_kernel(
            tuple(a.reshape(s) for a, s in zip(xs, shapes)), theta, rotary,
            first, inv_freq, scale, copies, scales and tuple(scales))
    else:
        # an array at a time, a copy a row of its own (in this order: the
        # older models' lowered steps are pinned by their text)
        out = tuple(_rope_product(
            a.reshape(B * copies, S // copies, *s[2:]), theta, rotary,
            interleave, first, inv_freq, scale).reshape(s)
            for a, s in zip(xs, shapes))
        if scales is not None:
            out = tuple(a if factor is None else a * factor
                        for a, factor in zip(out, scales))
    return out if isinstance(x, tuple) else out[0]


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
    ``blk`` holds ``wg``, an output gate: ``wo`` reads ``attn *
    sigmoid(norm(x) wg)``, made in float32, a channel each (``wg`` D x H x
    hd) or a scalar a head (D x H).

    Where the configuration has them (a ``HybridConfig``): ``attn_window``,
    the keys a query reads, its own among them (0: every earlier one; the
    band's layers run under the scope ``window`` inside ``attn``);
    ``rope_rotary``, how many of a head's first lanes rotate (None: all);
    ``rope_yarn``, a :class:`Yarn` whose table and scale the rotary pass
    takes; ``norm_after``: the projections read ``x`` itself and
    ``attn_norm`` norms ``wo``'s output, ``x + norm(wo(...))``;
    ``sandwich_norm``: a second vector, ``attn_norm_2``, norms ``wo``'s
    output besides the first's norm of its input, ``x + norm_2(wo(...))``."""
    dt = config.dtype
    after = getattr(config, "norm_after", False)
    B, S, D = x.shape
    H, KV, hd = config.n_head, config.n_kv_head, config.head_dim
    window = getattr(config, "attn_window", 0)
    rotary, yarn = (getattr(config, name, None)
                    for name in ("rope_rotary", "rope_yarn"))
    turn = partial(rope, theta=config.rope_theta)
    copies = 2 if config.block_length else 1
    # Where the attention would scale q itself before its kernel and the
    # rotary pass is a kernel too, that one scales q on its way out: between
    # two Mosaic calls the scale is a pass of its own over q.  (Elsewhere
    # the trace stays as it was: XLA fuses the scale into the product.)
    scaled = (config.rope_theta is not None and scales_q(config.attn_impl)
              and _rope_takes_kernel([(B, S, H, hd), (B, S, KV, hd)], False,
                                     copies))
    sm_scale = 1.0 if scaled else None
    if rotary is not None or yarn is not None:
        turn = partial(
            turn, rotary=rotary, first=True, scale=yarn.scale if yarn else 1.0,
            inv_freq=yarn and yarn.inv_freq(rotary or hd, config.rope_theta))
    with jax.named_scope("attn"), jax.named_scope("window") if window \
            else contextlib.nullcontext():
        h = x if after else rmsnorm(x, blk["attn_norm"],
                                    config.rms_eps).astype(dt)
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
            # rotates as a row of its own.
            q, k = turn((q, k), hd=hd, copies=copies,
                        scales=(hd ** -0.5, None) if scaled else None)
        q, k, v = (checkpoint_name(a, remat.QKV) for a in (q, k, v))
        # GQA: k and v go in at KV heads; the splash kernel takes them so,
        # and the dispatcher repeats them for the paths that cannot.
        if config.block_length:
            attn = block_diffusion_attention(q, k, v, config.block_length,
                                             config.attn_impl, sm_scale)
        elif window:
            attn = window_attention(q, k, v, window, config.attn_impl,
                                    sm_scale)
        else:
            attn = causal_attention(q, k, v, config.attn_impl, sm_scale)
        attn = attn.astype(dt).reshape(B, S, H * hd)
        if "wg" in blk:
            gate = jax.nn.sigmoid(
                dense(h, blk, "wg", axes, dt).astype(jnp.float32))
            if gate.shape[-1] == H:  # a scalar a head
                gate = jnp.repeat(gate, hd, axis=-1)
            attn = (attn.astype(jnp.float32) * gate).astype(dt)
        out = dense(attn, blk, "wo", axes, dt)
        if after:
            out = rmsnorm(out, blk["attn_norm"], config.rms_eps).astype(dt)
        if getattr(config, "sandwich_norm", False):
            out = rmsnorm(out, blk["attn_norm_2"], config.rms_eps).astype(dt)
        return x + out


def _feed_forward_out(x, blk, config, axes, **expert_layer):
    """The feed-forward half without its residual add, inside the caller's
    ``mlp`` scope -> (its output, what the expert layer says of itself)."""
    dt = config.dtype
    after = getattr(config, "norm_after", False)
    h = x if after else rmsnorm(x, blk["mlp_norm"], config.rms_eps)
    if "router" in blk:
        # the router reads the norm's float32 output, the experts its
        # cast to the compute dtype
        y, router_losses, counts = _moe.moe_mlp(
            h, blk, experts_per_token=config.experts_per_token,
            norm_topk_prob=config.norm_topk_prob, dtype=dt,
            first_held=config.held.start, **expert_layer)
        return y, (router_losses, counts)
    h = h.astype(dt)
    gate = checkpoint_name(dense(h, blk, "w_gate", axes, dt),
                           remat.GATE_UP)
    up = checkpoint_name(dense(h, blk, "w_up", axes, dt), remat.GATE_UP)
    act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    out = dense(act.astype(dt), blk, "w_down", axes, dt)
    if after:
        out = rmsnorm(out, blk["mlp_norm"], config.rms_eps).astype(dt)
    if getattr(config, "sandwich_norm", False):
        out = rmsnorm(out, blk["mlp_norm_2"], config.rms_eps).astype(dt)
    return out, None


def feed_forward(x, blk, config, axes, **expert_layer):
    """The feed-forward half.  -> (x + its output, what the expert layer
    says of itself): the second is ``None`` for a dense MLP; with experts
    (``blk`` holds a ``router``) it is (moe.router_losses' pair, the layer's
    counts: ``moe.moe_mlp``).  Reads ``dtype``, ``rms_eps`` and, with experts,
    ``experts_per_token``, ``norm_topk_prob`` and ``held``; of ``blk``
    ``mlp_norm`` and the SwiGLU's ``w_gate``, ``w_up``, ``w_down``, or what
    ``moe.moe_mlp`` reads, which is also handed ``expert_layer``.  Where the
    configuration has ``norm_after`` the dense MLP reads ``x`` itself and
    ``mlp_norm`` norms its output, ``x + norm(down(...))``; where it has
    ``sandwich_norm``, ``mlp_norm_2`` norms the output besides (no expert
    layer has either)."""
    with jax.named_scope("mlp"):
        out, said = _feed_forward_out(x, blk, config, axes, **expert_layer)
        return x + out, said


def feed_forward_branch(u, blk, config, axes, **expert_layer):
    """:func:`feed_forward` without its residual add -> (its output over
    ``norm(u)``, what the expert layer says of itself): what a residual of
    several streams (``models/streams.py``) runs between its read and its
    write."""
    with jax.named_scope("mlp"):
        return _feed_forward_out(u, blk, config, axes, **expert_layer)
