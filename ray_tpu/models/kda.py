"""The KDA mixer: the layer of a hybrid decoder (``models/hybrid.py``) whose
token mixing is the gated delta rule with a per-channel decay
(``ops/kda.py``), as Solar-Open2 and Kimi-Linear have it (kind ``K``).  Not a
model; the file is the mixer, its parameters and its sizes, with the
interface ``hybrid.KINDS`` asks of a kind.

Per layer, on ``u = norm(x)`` (H heads of d channels, ``inner = H x d``; the
two low-rank gates pass through d channels, ``kda_use_full_proj`` false):

    q~, k~, v~ = silu(conv(u wq)), silu(conv(u wk)), silu(conv(u wv))
                                     causal, depthwise, K taps, no bias
                                     (``mamba2.causal_conv``)
    q = q~ / |q~| d^-1/2,  k = k~ / |k~|          a head, float32
    g = -exp(A_log) softplus((u w_fa) w_fb + dt_bias)   (H, d) a position,
                                     float32, non-positive: the decay's log
    beta = 2 sigmoid(u w_beta)       a head, in (0, 2): an eigenvalue of
                                     ``I - beta k k^T`` may be negative
    o = kda(q, k, v, g, beta)        ops/kda.py
    y = norm_d(o) * sigmoid((u w_ga) w_gb + b_g)    RMSNorm over each head's
                                     d channels, one weight of d
    out = y wo

**A share of the heads** is a smaller H: the matrices hold the columns (``wo``
the rows) of the heads held here, ``w_fa`` and ``w_ga`` are whole, and what
the absent heads would add to ``y wo`` is left out.

The projections multiply in the compute dtype with float32 accumulation;
the convolutions, the norms and the gates are float32 passes that round
once; ``g`` and ``beta`` stay float32 into the scan.

Scopes: the whole mixer is ``kda``, inside it ``kda_conv`` (the three
convolutions' shifted multiply-adds and the silu) and ``kda_scan``
(``ops/kda.py``).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.layers import dense, rmsnorm, stacked_normal
from ray_tpu.models.mamba2 import short_conv
from ray_tpu.ops import remat
from ray_tpu.ops.kda import SUB, kda
from ray_tpu.ops.kda import path as kda_path

#: under the square root of q's and k's L2 norms
L2_EPS = 1e-6


def init_params(config, key, n: int, out_std: float) -> Dict[str, Any]:
    """``n`` mixers stacked on a leading axis.  Matrices normal(0.02), ``wo``
    normal(``out_std``); ``A_log`` the log of uniform [1, 16] a head;
    ``dt_bias`` the inverse softplus of a log-uniform draw in
    [``time_step_min``, ``time_step_max``] floored at ``time_step_floor``, a
    channel; the norms ones; ``b_g`` uniform in +-1/sqrt(d) as the bias of a
    ``Linear`` over d inputs starts; the convolutions uniform in
    +-1/sqrt(taps) as a depthwise ``Conv1d`` starts."""
    D, H, d, K = (config.d_model, config.kda_heads, config.kda_head_dim,
                  config.kda_conv)
    inner = H * d
    ks = jax.random.split(key, 15)
    norm = partial(stacked_normal, n)

    dt = jnp.exp(jax.random.uniform(ks[0], (n, inner)) * (
        math.log(config.time_step_max) - math.log(config.time_step_min))
        + math.log(config.time_step_min))
    dt = jnp.maximum(dt, config.time_step_floor)
    bound = 1.0 / math.sqrt(K)
    params = {
        "kda_norm": jnp.ones((n, D)),
        "wq": norm(ks[1], (D, inner)), "wk": norm(ks[2], (D, inner)),
        "wv": norm(ks[3], (D, inner)),
        "w_fa": norm(ks[4], (D, d)), "w_fb": norm(ks[5], (d, inner)),
        "w_ga": norm(ks[6], (D, d)), "w_gb": norm(ks[7], (d, inner)),
        "b_g": jax.random.uniform(ks[14], (n, inner), minval=-d ** -0.5,
                                  maxval=d ** -0.5),
        "w_beta": norm(ks[8], (D, H)),
        "A_log": jnp.log(jax.random.uniform(ks[9], (n, H), minval=1.0,
                                            maxval=16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "head_norm": jnp.ones((n, d)),
        "wo": norm(ks[10], (inner, D), out_std),
    }
    for name, k in zip("qkv", ks[11:]):
        params["conv_" + name] = jax.random.uniform(
            k, (n, K, inner), minval=-bound, maxval=bound)
    return params


def logical_axes(config) -> Dict[str, Any]:
    """Of the stacked leaves: the head-wide projections cut as attention's
    are (`embed` over `fsdp`, the heads over `tensor`), the low-rank halves
    that every head reads and the vectors whole."""
    L = "layers"
    axes = {
        "kda_norm": (L, "norm"),
        "wq": (L, "embed", "heads"), "wk": (L, "embed", "heads"),
        "wv": (L, "embed", "heads"), "wo": (L, "heads", "embed"),
        "w_fa": (L, "embed", None), "w_fb": (L, None, None),
        "w_ga": (L, "embed", None), "w_gb": (L, None, None),
        "b_g": (L, None), "w_beta": (L, "embed", None),
        "A_log": (L, None), "dt_bias": (L, None), "head_norm": (L, "norm"),
    }
    axes.update({"conv_" + name: (L, None, None) for name in "qkv"})
    return axes


def matmul_params(config, routed: float) -> int:
    """The matrix entries of one mixer that a position meets."""
    D, H, d = config.d_model, config.kda_heads, config.kda_head_dim
    return 4 * D * H * d + 2 * (D * d + d * H * d) + D * H


def num_params(config) -> int:
    """Of one mixer, its pre-norm included."""
    H, d = config.kda_heads, config.kda_head_dim
    return (matmul_params(config, 0) + 3 * config.kda_conv * H * d
            + 2 * H * d + H + d + config.d_model)


def mixer_flops(config, seq_len: int) -> float:
    """Forward FLOPs a position of ``ops/kda.py``'s products: ``A`` and ``B``
    at the causal half, ``T [V | Kbar]`` at the triangular half, ``B U``, and
    the three d x d products with the state."""
    H, d = config.kda_heads, config.kda_head_dim
    C = min(config.kda_chunk, seq_len)
    return 2.0 * H * (2.5 * C * d + 3 * d * d)


def _scan_path(config, tokens: int, seq_len: int) -> str:
    """``ops.kda.path`` for a chip's ``tokens`` under the ambient mesh."""
    mesh, rows = remat.rows_under_mesh(tokens, seq_len)
    return kda_path((rows, seq_len, config.kda_heads, config.kda_head_dim),
                    min(config.kda_chunk, seq_len), mesh)


def layer_bytes(config, tokens: int, seq_len: int, tensor: int,
                itemsize: int):
    """For ``hybrid._layer_sizes``, a chip's bytes of one layer over
    ``tokens`` positions with the heads cut ``tensor`` ways, under the path
    ``ops.kda.path`` picks here: (its working set; nothing kept for the
    backward beside its input; the rung it names: the three projections the
    convolutions read, ``remat.CONV_IN``, which spares their products).
    The working set a position: eight arrays as wide as the heads (q, k, v,
    o, the two gates, the decay's log and its cumulative sum in float32
    counted twice) and, a head, the chunk's three (chunk x chunk) matrices
    and what else the scan builds: with the kernels (``ops/kda_kernel.py``)
    each chunk's incoming state alone, the explicit decays and the scaled
    copies of q and k living in VMEM; with XLA's form the explicit decays
    inside a sub-chunk (SUB x d float32, two copies live), the keys scaled
    for each later sub-chunk and five more scaled copies of q and k."""
    d, chunk = config.kda_head_dim, min(config.kda_chunk, seq_len)
    sub = min(SUB, chunk)
    if _scan_path(config, tokens, seq_len) == "kernel":
        built = d * d * itemsize // chunk
    else:
        built = 2 * sub * d * 4 + (chunk // sub + 5) * d * itemsize
    wide = 3 * config.kda_heads * d // tensor
    return (tokens * (config.kda_heads * (
        d * (8 * itemsize + 2 * 4) + built + 3 * chunk * (4 + itemsize)))
        // tensor, 0,
        {remat.CONV_IN: (tokens * wide * itemsize, remat.spared(
            flops=2.0 * tokens * config.d_model * wide))})


def first_call_facts(config, rows: int, seq_len: int) -> Dict[str, Any]:
    chunk = min(config.kda_chunk, seq_len)
    return {"kda_heads": config.kda_heads, "kda_head_dim": config.kda_head_dim,
            "kda_chunk": chunk, "kda_chunks": rows * seq_len // chunk}


def l2norm(x):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def mixer(x, blk, config, axes):
    """``x + wo(...)``: the layer.  x: (B, S, D) in the compute dtype;
    ``blk`` one layer of :func:`init_params`; ``axes`` of its stack."""
    dt, f32 = config.dtype, jnp.float32
    B, S, _ = x.shape
    H, d = config.kda_heads, config.kda_head_dim
    no_bias = jnp.zeros((H * d,), f32)
    with jax.named_scope("kda"):
        u = rmsnorm(x, blk["kda_norm"], config.rms_eps).astype(dt)
        q, k, v = (checkpoint_name(dense(u, blk, name, axes, dt),
                                   remat.CONV_IN)
                   for name in ("wq", "wk", "wv"))
        with jax.named_scope("kda_conv"):
            q, k, v = (short_conv(a, blk["conv_" + name],
                                  dt if name == "v" else f32, no_bias)
                       .reshape(B, S, H, d)
                       for name, a in zip("qkv", (q, k, v)))
        q, k, v = ((l2norm(q) * d ** -0.5).astype(dt), l2norm(k).astype(dt),
                   v.astype(dt))
        low = dense(u, blk, "w_fa", axes, dt)
        g = jnp.einsum("bsr,rc->bsc", low, blk["w_fb"].astype(dt),
                       preferred_element_type=f32)
        g = -jnp.exp(blk["A_log"].astype(f32))[:, None] * jax.nn.softplus(
            g + blk["dt_bias"]).reshape(B, S, H, d)
        beta = 2.0 * jax.nn.sigmoid(
            dense(u, blk, "w_beta", axes, dt).astype(f32))
        with jax.named_scope("kda_scan"):
            o = kda(q, k, v, g, beta, min(config.kda_chunk, S))
        low = dense(u, blk, "w_ga", axes, dt)
        gate = jnp.einsum("bsr,rc->bsc", low, blk["w_gb"].astype(dt),
                          preferred_element_type=f32) + blk["b_g"]
        y = rmsnorm(o, blk["head_norm"], config.rms_eps).reshape(B, S, H * d)
        y = (y * jax.nn.sigmoid(gate)).astype(dt)
        return x + dense(y, blk, "wo", axes, dt)


def layer(config, axes, index: int):
    """Layer ``index`` of the kind as (x, its row of the stack) -> (x,
    None)."""
    return lambda x, blk: (mixer(x, blk, config, axes), None)
