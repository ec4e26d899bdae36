"""The gated-delta-net mixer: the layer of a hybrid decoder
(``models/hybrid.py``) whose token mixing is the gated delta rule with one
decay a head (``ops/gdn.py``), keys narrower than values, as the
``linear_attention`` layers of the ``olmo_hybrid`` family (the Qwen3-Next
family's layer) have it (kind ``G``).  Not a model; the file is the mixer,
its parameters and its sizes, with the interface ``hybrid.KINDS`` asks of a
kind.

Per layer, on ``u`` (H heads, keys of dk channels, values of dv, K taps;
``u = norm(x)``, or with ``norm_after`` ``x`` itself):

    q~, k~, v = silu(conv(u wq)), silu(conv(u wk)), silu(conv(u wv))
                                     wq, wk: D x H dk; wv: D x H dv; causal,
                                     depthwise, K taps, no bias
                                     (``mamba2.causal_conv``)
    q = q~ / |q~| dk^-1/2,  k = k~ / |k~|         a head, float32
    g = -exp(A_log) softplus(u w_a + dt_bias)     one number a head a
                                     position, float32, non-positive
    beta = 2 sigmoid(u w_b)          a head, in (0, 2): an eigenvalue of
                                     ``I - beta k k^T`` may be negative
    o = gdn(q, k, v, g, beta)        ops/gdn.py
    y = norm_dv(o) * silu(u wg)      RMSNorm over each head's dv channels,
                                     one weight of dv; wg: D x H dv
    out = y wo                       wo: H dv x D

and the layer is ``x + out``, or with ``norm_after`` ``x + norm(out)``
(``hybrid.py``'s docstring; ``gdn_norm`` is the weight of either).  No bias
anywhere.

The projections multiply in the compute dtype with float32 accumulation;
the convolutions, the norms and the gates are float32 passes that round
once.  ``u w_a`` and ``u w_b`` leave their products in float32 and ``g`` and
``beta`` stay float32 into the scan: the decay is ``exp`` of sums of ``g``,
so an absolute error in ``u w_a`` is a relative one in every state after it,
and ``u`` is the residual stream itself, whose entries pass 1 (rounded to
the compute dtype a pre-activation of 4 is off by 0.016, a decay at
``exp(A_log)`` 16 by a quarter).

Scopes: the whole mixer is ``gdn``, inside it ``gdn_conv`` (the three
convolutions' shifted multiply-adds and the silu) and ``gdn_scan``
(``ops/gdn.py``).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.kda import l2norm
from ray_tpu.models.layers import dense, rmsnorm, stacked_normal
from ray_tpu.models.mamba2 import short_conv
from ray_tpu.ops import remat
from ray_tpu.ops.gdn import gdn
from ray_tpu.ops.gdn import path as gdn_path
from ray_tpu.ops.kda import SUB

#: the kind reads ``norm_after`` (``hybrid.HybridConfig``)
NORM_AFTER = True


def _dims(config):
    """(heads, a head's key channels, its value channels)."""
    return config.gdn_heads, config.gdn_key_dim, config.gdn_value_dim


def init_params(config, key, n: int, out_std: float) -> Dict[str, Any]:
    """``n`` mixers stacked on a leading axis.  Matrices normal(0.02), ``wo``
    normal(``out_std``); ``A_log`` the log of uniform [1, 16] a head;
    ``dt_bias`` the inverse softplus of a log-uniform draw in
    [``time_step_min``, ``time_step_max``] floored at ``time_step_floor``, a
    head; the norms ones; the convolutions uniform in +-1/sqrt(taps) as a
    depthwise ``Conv1d`` starts."""
    D, K = config.d_model, config.gdn_conv
    H, dk, dv = _dims(config)
    ks = jax.random.split(key, 12)
    norm = partial(stacked_normal, n)

    dt = jnp.exp(jax.random.uniform(ks[0], (n, H)) * (
        math.log(config.time_step_max) - math.log(config.time_step_min))
        + math.log(config.time_step_min))
    dt = jnp.maximum(dt, config.time_step_floor)
    bound = 1.0 / math.sqrt(K)
    params = {
        "gdn_norm": jnp.ones((n, D)),
        "wq": norm(ks[1], (D, H * dk)), "wk": norm(ks[2], (D, H * dk)),
        "wv": norm(ks[3], (D, H * dv)), "wg": norm(ks[4], (D, H * dv)),
        "w_a": norm(ks[5], (D, H)), "w_b": norm(ks[6], (D, H)),
        "A_log": jnp.log(jax.random.uniform(ks[7], (n, H), minval=1.0,
                                            maxval=16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "head_norm": jnp.ones((n, dv)),
        "wo": norm(ks[8], (H * dv, D), out_std),
    }
    for name, k, width in zip("qkv", ks[9:], (dk, dk, dv)):
        params["conv_" + name] = jax.random.uniform(
            k, (n, K, H * width), minval=-bound, maxval=bound)
    return params


def logical_axes(config) -> Dict[str, Any]:
    """Of the stacked leaves: the head-wide projections cut as attention's
    are (`embed` over `fsdp`, the heads over `tensor`), the vectors whole."""
    L = "layers"
    axes = {
        "gdn_norm": (L, "norm"),
        "wq": (L, "embed", "heads"), "wk": (L, "embed", "heads"),
        "wv": (L, "embed", "heads"), "wg": (L, "embed", "heads"),
        "wo": (L, "heads", "embed"),
        "w_a": (L, "embed", None), "w_b": (L, "embed", None),
        "A_log": (L, None), "dt_bias": (L, None), "head_norm": (L, "norm"),
    }
    axes.update({"conv_" + name: (L, None, None) for name in "qkv"})
    return axes


def matmul_params(config, routed: float) -> int:
    """The matrix entries of one mixer that a position meets."""
    H, dk, dv = _dims(config)
    return config.d_model * H * (2 * dk + 3 * dv + 2)


def num_params(config) -> int:
    """Of one mixer, its norm included."""
    H, dk, dv = _dims(config)
    return (matmul_params(config, 0) + config.gdn_conv * H * (2 * dk + dv)
            + 2 * H + dv + config.d_model)


def mixer_flops(config, seq_len: int) -> float:
    """Forward FLOPs a position of ``ops/gdn.py``'s products: ``A`` and ``B``
    at the causal half (C x dk / 2 multiply-adds each), ``T [V | Kbar]`` at
    the triangular half (C x (dv + dk) / 2), ``B U`` (C x dv / 2), and the
    three dk x dv products with the state."""
    H, dk, dv = _dims(config)
    C = min(config.gdn_chunk, seq_len)
    return 2.0 * H * (C * (1.5 * dk + dv) + 3 * dk * dv)


def _scan_path(config, tokens: int, seq_len: int) -> str:
    """``ops.gdn.path`` for a chip's ``tokens`` under the ambient mesh."""
    mesh, rows = remat.rows_under_mesh(tokens, seq_len)
    H, dk, dv = _dims(config)
    return gdn_path((rows, seq_len, H, dk), (rows, seq_len, H, dv),
                    min(config.gdn_chunk, seq_len), mesh)


def layer_bytes(config, tokens: int, seq_len: int, tensor: int,
                itemsize: int):
    """For ``hybrid._layer_sizes``, a chip's bytes of one layer over
    ``tokens`` positions with the heads cut ``tensor`` ways, under the path
    ``ops.gdn.path`` picks here: (its working set; nothing kept for the
    backward beside its input; the rungs it names).  The rungs: the three
    projections the convolutions read (``remat.CONV_IN``; keeping them
    spares their products) and the chunks' inverse (``remat.INVERSE``: ``T``
    in the compute dtype and, with the kernels, the float32 ``X`` their
    backward reads beside it; keeping it spares the substitution's ``SUB``
    dependent steps and the joins, passes bound by memory over the (C x C)
    float32 blocks).  The working set a position and a head, with the
    kernels (``ops/gdn_kernel.py``: ``Gamma``, ``B``, the scaled copies and
    the state stay in VMEM): q, k, v as projected, after the convolution
    and laid out for the kernels, with their cotangents, some four arrays
    dk wide and six dv wide at a time (o, the gate and theirs among them);
    ``A`` and ``X`` in float32 and ``T`` twice in the compute dtype; each
    chunk's incoming state in float32, its value channels
    padded to the chip's tiles of 128 lanes.  With XLA's form: q and k with
    their scaled copies (``Kbar``, ``Qbar``, ``K e^{G_C - G}``, ``T Kbar``)
    and cotangents, ten arrays dk wide; v, the convolution's float32 v, ``T
    V``, ``U``, o, the gate and their cotangents, twelve dv wide; and the
    chunk's ``Gamma``, ``A``, ``B`` and ``T`` in float32 with ``B`` and
    ``T`` once more in the compute dtype, each with a cotangent."""
    H, dk, dv = _dims(config)
    H //= tensor
    chunk = min(config.gdn_chunk, seq_len)
    if _scan_path(config, tokens, seq_len) == "kernel":
        working = H * ((4 * dk + 6 * dv) * itemsize
                       + chunk * (2 * 4 + 2 * itemsize)
                       + dk * -(-dv // 128) * 128 * 4 // chunk)
        inverse = H * chunk * (4 + itemsize)
    else:
        working = H * (10 * dk * itemsize + 12 * dv * itemsize
                       + 2 * chunk * (4 * 4 + 2 * itemsize))
        inverse = H * chunk * itemsize
    sub = min(SUB, chunk)
    passes = 2 * (sub + 3 * int(math.log2(chunk // sub)))
    wide = H * (2 * dk + dv)
    return (tokens * working, 0, {
        remat.INVERSE: (tokens * inverse, remat.spared(
            moved=passes * tokens * H * chunk * 4)),
        remat.CONV_IN: (tokens * wide * itemsize, remat.spared(
            flops=2.0 * tokens * config.d_model * wide))})


def first_call_facts(config, rows: int, seq_len: int) -> Dict[str, Any]:
    H, dk, dv = _dims(config)
    chunk = min(config.gdn_chunk, seq_len)
    return {"gdn_heads": H, "gdn_key_dim": dk, "gdn_value_dim": dv,
            "gdn_chunk": chunk, "gdn_chunks": rows * seq_len // chunk}


def mixer(x, blk, config, axes):
    """The layer.  x: (B, S, D) in the compute dtype; ``blk`` one layer of
    :func:`init_params`; ``axes`` of its stack."""
    dt, f32 = config.dtype, jnp.float32
    B, S, _ = x.shape
    H, dk, dv = _dims(config)
    with jax.named_scope("gdn"):
        u = x if config.norm_after else rmsnorm(
            x, blk["gdn_norm"], config.rms_eps).astype(dt)
        q, k, v = (checkpoint_name(dense(u, blk, name, axes, dt),
                                   remat.CONV_IN)
                   for name in ("wq", "wk", "wv"))
        with jax.named_scope("gdn_conv"):
            q, k, v = (short_conv(a, blk["conv_" + name],
                                  dt if name == "v" else f32)
                       .reshape(B, S, H, -1)
                       for name, a in zip("qkv", (q, k, v)))
        q, k, v = ((l2norm(q) * dk ** -0.5).astype(dt), l2norm(k).astype(dt),
                   v.astype(dt))
        a, b = (jnp.einsum("bsd,dh->bsh", u, blk[name].astype(dt),
                           preferred_element_type=f32)
                for name in ("w_a", "w_b"))
        g = -jnp.exp(blk["A_log"].astype(f32)) * jax.nn.softplus(
            a + blk["dt_bias"])
        beta = 2.0 * jax.nn.sigmoid(b)
        with jax.named_scope("gdn_scan"):
            o = gdn(q, k, v, g, beta, min(config.gdn_chunk, S))
        gate = jax.nn.silu(dense(u, blk, "wg", axes, dt).astype(f32))
        y = rmsnorm(o, blk["head_norm"], config.rms_eps).reshape(
            B, S, H * dv)
        out = dense((y * gate).astype(dt), blk, "wo", axes, dt)
        if config.norm_after:
            out = rmsnorm(out, blk["gdn_norm"], config.rms_eps).astype(dt)
        return x + out


def layer(config, axes, index: int):
    """Layer ``index`` of the kind as (x, its row of the stack) -> (x,
    None)."""
    return lambda x, blk: (mixer(x, blk, config, axes), None)
