"""The Mamba-2 mixer: the layer of a hybrid decoder (``models/hybrid.py``)
whose token mixing is a state-space recurrence (``ops/ssd.py``) and not
attention (kind ``M``).  Not a model; the file is the mixer, its parameters
and its sizes, with the interface ``hybrid.KINDS`` asks of a kind.

Per layer, on ``u = norm(x)`` (H heads of P channels, G groups that share B
and C, a state of N; ``d_inner = H x P``):

    [z | xBC | dt] = in_proj(u)            widths d_inner | d_inner + 2GN | H
    xBC = silu(conv(xBC) + conv_b)         causal, depthwise, K taps, zeros
                                           before a row's first position
    [x | B | C] = xBC                      x as (H, P), B and C as (G, N)
    delta = softplus(dt + dt_bias)         a head, float32
    y = ssd(x, delta, -exp(A_log), B, C, D)
    y = norm_g(y * silu(z))                RMSNorm over each of the G groups
                                           of d_inner / G channels, one
                                           weight of d_inner
    out = out_proj(y)

No bias but the convolution's.  The two projections multiply in the compute
dtype with float32 accumulation; the convolution, the gate and the grouped
norm are float32 passes over (tokens, d_inner)-sized arrays that round once;
``delta`` and ``A`` stay float32 into the scan.

``causal_conv(x, w, b)`` serves three kinds: this one and ``K``
(``models/kda.py``) at four taps with a bias, and ``C``
(``models/shortconv.py``) at three without one (``b=None``); the number of
taps is ``w``'s leading size.

Scopes: the whole mixer is ``ssm``, inside it ``ssm_conv`` (the four shifted
multiply-adds, the bias and the silu) and ``ssm_scan`` (``ops/ssd.py``: two
Pallas kernels, ``ops/ssd_kernel.py``, where the sizes lie on the chip's
tiles and the placement lets a Mosaic call sit, as at the published sizes on
one chip or under a mesh over rows and groups; XLA einsums elsewhere.  The
call passes no choice: ``ssd`` reads the shapes and the mesh).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.layers import dense, rmsnorm
from ray_tpu.ops import conv_kernel, remat
from ray_tpu.ops.ssd import path as ssd_path
from ray_tpu.ops.ssd import ssd


def widths(config) -> Dict[str, int]:
    """The mixer's sizes from a configuration's ``ssm_heads`` (H),
    ``ssm_head_dim`` (P), ``ssm_groups`` (G), ``ssm_state`` (N)."""
    inner = config.ssm_heads * config.ssm_head_dim
    conv = inner + 2 * config.ssm_groups * config.ssm_state
    return {"inner": inner, "conv": conv,
            "in_proj": inner + conv + config.ssm_heads}


def init_params(config, key, n: int, out_std: float) -> Dict[str, Any]:
    """``n`` mixers stacked on a leading axis.  Matrices normal(0.02),
    ``out_proj`` normal(``out_std``) (the residual's rescaling is the
    decoder's); ``A_log`` the log of uniform [1, 16]; ``dt_bias`` the inverse
    softplus of a log-uniform draw in [``time_step_min``, ``time_step_max``]
    floored at ``time_step_floor``; ``D`` and both norms ones; the
    convolution uniform in +-1/sqrt(taps) as a depthwise ``Conv1d`` starts."""
    D, H, K = config.d_model, config.ssm_heads, config.ssm_conv
    w = widths(config)
    ks = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(ks[2], (n, H)) * (
        math.log(config.time_step_max) - math.log(config.time_step_min))
        + math.log(config.time_step_min))
    dt = jnp.maximum(dt, config.time_step_floor)
    bound = 1.0 / math.sqrt(K)
    return {
        "ssm_norm": jnp.ones((n, D)),
        "in_proj": jax.random.normal(ks[0], (n, D, w["in_proj"])) * 0.02,
        "conv_w": jax.random.uniform(ks[3], (n, K, w["conv"]),
                                     minval=-bound, maxval=bound),
        "conv_b": jax.random.uniform(ks[4], (n, w["conv"]),
                                     minval=-bound, maxval=bound),
        "A_log": jnp.log(jax.random.uniform(ks[5], (n, H), minval=1.0,
                                            maxval=16.0)),
        "D": jnp.ones((n, H)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "gate_norm": jnp.ones((n, w["inner"])),
        "out_proj": jax.random.normal(ks[1], (n, w["inner"], D)) * out_std,
    }


def logical_axes(config) -> Dict[str, Any]:
    """Of the stacked leaves: the projections cut as an MLP's are (`embed`
    over `fsdp`, the inner width over `tensor`), the vectors whole."""
    L = "layers"
    return {
        "ssm_norm": (L, "norm"),
        "in_proj": (L, "embed", "mlp"),
        "conv_w": (L, None, None),
        "conv_b": (L, None),
        "A_log": (L, None),
        "D": (L, None),
        "dt_bias": (L, None),
        "gate_norm": (L, "norm"),
        "out_proj": (L, "mlp", "embed"),
    }


def matmul_params(config, routed: float) -> int:
    """The matrix entries of one mixer that a position meets."""
    w = widths(config)
    return config.d_model * w["in_proj"] + w["inner"] * config.d_model


def num_params(config) -> int:
    """Of one mixer, its pre-norm included."""
    w = widths(config)
    return (config.d_model * w["in_proj"] + (config.ssm_conv + 1) * w["conv"]
            + 3 * config.ssm_heads + w["inner"] + w["inner"] * config.d_model
            + config.d_model)


def mixer_flops(config, seq_len: int) -> float:
    """Forward FLOPs a position of the scan's four products a chunk
    (``ops/ssd.py``): a position's share of C B^T a group (Q x Q x N) and
    (L o C B^T)(delta x) a head (Q x Q x P), both at the causal half; the
    chunk's state and C times the incoming state a head (Q x P x N each)."""
    Q = min(config.ssm_chunk, seq_len)
    H, P, G, N = (config.ssm_heads, config.ssm_head_dim, config.ssm_groups,
                  config.ssm_state)
    return 2.0 * (G * Q * N / 2 + H * Q * P / 2 + 2 * H * P * N)


def _scan_path(config, tokens: int, seq_len: int) -> str:
    """``ops.ssd.path`` for a chip's ``tokens`` under the ambient mesh."""
    mesh, rows = remat.rows_under_mesh(tokens, seq_len)
    H, G = config.ssm_heads, config.ssm_groups
    return ssd_path((rows, seq_len, H, config.ssm_head_dim),
                    (rows, seq_len, G, config.ssm_state),
                    min(config.ssm_chunk, seq_len), mesh)


def layer_bytes(config, tokens: int, seq_len: int, tensor: int,
                itemsize: int):
    """For ``hybrid._layer_sizes``, a chip's bytes of one layer over
    ``tokens`` positions with the inner width cut ``tensor`` ways, under
    the path ``ops.ssd.path`` picks here: (its working set; nothing kept for
    the backward beside its input; the rung it names: ``in_proj``'s output,
    ``[z | xBC | dt]`` (``remat.SSM_IN``), which spares the product that
    made it).  The working set: four arrays as wide as ``in_proj``'s output
    (the projection, its cotangent and the passes between them in the
    compute dtype and float32) and what the scan leaves in HBM.  With the
    kernels (``ops/ssd_kernel.py``) that is each chunk's incoming state, a
    head (P x N in the compute dtype), the cumulative sums and ``delta`` in
    two layouts; the (chunk x chunk) decays live in VMEM.  With XLA's form
    it is those decays a head and a chunk position, two float32 and a
    compute-dtype copy each way (the compiler fuses the rest of them
    away)."""
    w = widths(config)
    H, chunk = config.ssm_heads // tensor, min(config.ssm_chunk, seq_len)
    if _scan_path(config, tokens, seq_len) == "kernel":
        scan = H * (config.ssm_head_dim * config.ssm_state * itemsize
                    // chunk + 6 * 4)
    else:
        scan = H * chunk * 2 * (2 * 4 + itemsize)
    wide = w["in_proj"] // tensor
    return (tokens * (4 * wide * itemsize + scan), 0,
            {remat.SSM_IN: (tokens * wide * itemsize, remat.spared(
                flops=2.0 * tokens * config.d_model * wide))})


def first_call_facts(config, rows: int, seq_len: int) -> Dict[str, Any]:
    chunk = min(config.ssm_chunk, seq_len)
    return {"ssm_heads": config.ssm_heads, "ssm_state": config.ssm_state,
            "ssm_chunk": chunk, "ssm_chunks": rows * seq_len // chunk}


def _taps(x, w, back: bool):
    """``sum_k w_k x_{t-(K-1)+k}`` over positions (``back``: ``sum_k w_k
    x_{t+(K-1)-k}``, the same taps read forwards), zeros beyond the row; K
    shifted multiply-adds in float32 over x as it is stored."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (0, K - 1) if back else (K - 1, 0), (0, 0)))
    total = 0.0
    for k in range(K):
        at = K - 1 - k if back else k
        total = total + padded[:, at:at + S].astype(jnp.float32) \
            * w[k].astype(jnp.float32)
    return total


@jax.custom_vjp
def causal_conv(x, w, b):
    """x: (B, S, C); w: (K, C), any number of taps K, tap K-1 the position
    itself; b: (C,), or None for a convolution without a bias
    (``models/shortconv.py``: three taps, ``conv_bias`` false).  Depthwise:
    ``y_t = b + sum_k w_k x_{t-(K-1)+k}`` in float32, zeros before the row's
    first position.  The backward is written out, because it is the same
    pass run the other way (``dx_t = sum_k w_k dy_{t+(K-1)-k}``) and K
    reductions for the taps; autodiff's transpose of the pad and the K
    slices is K padded float32 copies of ``dy`` summed."""
    y = _taps(x, w, False)
    return y if b is None else y + b.astype(jnp.float32)


def _causal_conv_fwd(x, w, b):
    return causal_conv(x, w, b), (x, w, b)


def _causal_conv_bwd(saved, dy):
    x, w, b = saved
    K, S = w.shape[0], x.shape[1]
    dy = dy.astype(jnp.float32)
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    dw = jnp.stack([jnp.sum(dy * padded[:, k:k + S].astype(jnp.float32),
                            axis=(0, 1)) for k in range(K)])
    return (_taps(dy, w, True).astype(x.dtype), dw.astype(w.dtype),
            None if b is None else jnp.sum(dy, axis=(0, 1)).astype(b.dtype))


causal_conv.defvjp(_causal_conv_fwd, _causal_conv_bwd)


def short_conv(x, w, out_dtype, xla_bias=None):
    """``silu(causal_conv(x, w, None))`` of a mixer's q, k or v (kinds `K`,
    `G`): ``ops/conv_kernel.py``'s pass where its rule says so, rounded once
    to ``out_dtype`` (float32 where the caller goes on in float32, as q and k
    under their norm); else XLA's form in float32, which the caller rounds.
    ``xla_bias``: what XLA's form adds (kind `K`'s zeros, which its lowered
    text holds)."""
    if conv_kernel.engaged(x.shape, w.shape[0]):
        return conv_kernel.conv(x, w, None, act=True, out_dtype=out_dtype)
    return jax.nn.silu(causal_conv(x, w, xla_bias))


def gated_norm(y, z, scale, groups: int, eps: float):
    """``rmsnorm(y * silu(z))`` over each of ``groups`` runs of the last
    axis by itself (``norm_before_gate`` false), one weight over them all;
    float32."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    split = gated.reshape(*gated.shape[:-1], groups, -1)
    ms = jnp.mean(split * split, axis=-1, keepdims=True)
    return (split * jax.lax.rsqrt(ms + eps)).reshape(gated.shape) * scale


def mixer(x, blk, config, axes):
    """``x + out_proj(...)``: the layer.  x: (B, S, D) in the compute dtype;
    ``blk`` one layer of :func:`init_params`; ``axes`` of its stack."""
    dt = config.dtype
    B, S, _ = x.shape
    H, P, G, N = (config.ssm_heads, config.ssm_head_dim, config.ssm_groups,
                  config.ssm_state)
    w = widths(config)
    with jax.named_scope("ssm"):
        u = rmsnorm(x, blk["ssm_norm"], config.rms_eps).astype(dt)
        zxbcdt = checkpoint_name(dense(u, blk, "in_proj", axes, dt),
                                 remat.SSM_IN)
        z, xBC, delta = jnp.split(
            zxbcdt, [w["inner"], w["inner"] + w["conv"]], axis=-1)
        with jax.named_scope("ssm_conv"):
            spans = (w["inner"], G * N, G * N)
            if conv_kernel.engaged(zxbcdt.shape, blk["conv_w"].shape[0],
                                   w["inner"], spans):
                # xBC where the projection wrote it: no slice's copy
                xs, Bm, Cm = conv_kernel.conv(
                    zxbcdt, blk["conv_w"], blk["conv_b"], act=True,
                    out_dtype=dt, offset=w["inner"], widths=spans)
            else:
                xBC = jax.nn.silu(causal_conv(xBC, blk["conv_w"],
                                              blk["conv_b"])).astype(dt)
                xs, Bm, Cm = jnp.split(
                    xBC, [w["inner"], w["inner"] + G * N], axis=-1)
        delta = jax.nn.softplus(delta.astype(jnp.float32) + blk["dt_bias"])
        with jax.named_scope("ssm_scan"):
            y = ssd(xs.reshape(B, S, H, P), delta, -jnp.exp(blk["A_log"]),
                    Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N), blk["D"],
                    min(config.ssm_chunk, S))
        y = gated_norm(y.reshape(B, S, w["inner"]), z, blk["gate_norm"], G,
                       config.gate_norm_eps).astype(dt)
        return x + dense(y, blk, "out_proj", axes, dt)


def layer(config, axes, index: int):
    """Layer ``index`` of the kind as (x, its row of the stack) -> (x,
    None)."""
    return lambda x, blk: (mixer(x, blk, config, axes), None)
