"""The gated short convolution: the layer of a hybrid decoder
(``models/hybrid.py``) whose token mixing is a causal depthwise convolution
of a few taps between two element-wise gates (kind ``C``; the ``conv``
layers of Liquid AI's ``lfm2`` / ``lfm2_moe`` families, ``conv_L_cache``
taps).  Not a model; the file is the mixer, its parameters and its sizes,
with the interface ``hybrid.KINDS`` asks of a kind.

Per layer, on ``h = norm(x)`` (every array ``d_model`` wide, K =
``conv_taps``):

    [B | C | u] = in_proj(h)               d_model x 3 d_model, cut in three
                                           in that order
    z   = B * u                            the input gate
    c_t = sum_k w_k z_{t-(K-1)+k}          causal, depthwise, K taps, zeros
                                           before a row's first position,
                                           no bias, no activation
    out = out_proj(C * c)                  the output gate

No bias anywhere.  The two projections multiply in the compute dtype with
float32 accumulation; the gate, the taps and the second gate are float32
from ``[B | C | u]`` to the one rounding of ``C * c``.

**The gate-convolve-gate pass is one function with a backward of its own**
(:func:`gated_conv`): it keeps ``[B | C | u]`` as ``in_proj`` wrote it and
the taps, nothing else, and makes ``z`` and ``c`` again in its backward
(two float32 arrays a layer that autodiff would keep).  The convolution and
its backward are ``models/mamba2.py:causal_conv``'s, without a bias.

Scopes: the whole mixer is ``shortconv``, inside it ``shortconv_gate`` (the
pass above: forward, backward and, under a checkpoint, the forward again).
The pass is XLA's: K shifted multiply-adds over a padded float32 copy, which
a v5e runs far from the array's bytes (``PERF.md``, PR 56); a kernel for it
is ROADMAP B's.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.layers import dense, rmsnorm, stacked_normal
from ray_tpu.models.mamba2 import causal_conv
from ray_tpu.ops import conv_kernel, remat


def init_params(config, key, n: int, out_std: float) -> Dict[str, Any]:
    """``n`` mixers stacked on a leading axis.  Matrices normal(0.02),
    ``out_proj`` normal(``out_std``), the norm ones, the taps uniform in
    +-1/sqrt(taps) as a depthwise ``Conv1d`` starts."""
    D, K = config.d_model, config.conv_taps
    ks = jax.random.split(key, 3)
    bound = 1.0 / math.sqrt(K)
    return {
        "conv_norm": jnp.ones((n, D)),
        "in_proj": stacked_normal(n, ks[0], (D, 3 * D)),
        "conv_w": jax.random.uniform(ks[1], (n, K, D), minval=-bound,
                                     maxval=bound),
        "out_proj": stacked_normal(n, ks[2], (D, D), out_std),
    }


def logical_axes(config) -> Dict[str, Any]:
    """Of the stacked leaves: the projections cut as an MLP's are, the taps
    whole."""
    L = "layers"
    return {"conv_norm": (L, "norm"), "in_proj": (L, "embed", "mlp"),
            "conv_w": (L, None, None), "out_proj": (L, "mlp", "embed")}


def matmul_params(config, routed: float) -> int:
    """The matrix entries of one mixer that a position meets."""
    return 4 * config.d_model * config.d_model


def num_params(config) -> int:
    """Of one mixer, its pre-norm included."""
    return matmul_params(config, 0) + (config.conv_taps + 1) * config.d_model


def mixer_flops(config, seq_len: int) -> float:
    """Forward FLOPs a position beside the matrices: a multiply-add a tap
    and a multiplication a gate, a channel."""
    return (2.0 * config.conv_taps + 2.0) * config.d_model


def layer_bytes(config, tokens: int, seq_len: int, tensor: int,
                itemsize: int):
    """For ``hybrid._layer_sizes``, a chip's bytes of one layer over
    ``tokens`` positions with the width cut ``tensor`` ways, under the form
    ``conv_kernel.path`` picks here: (its working set: ``[B | C | u]`` and
    its cotangent in the compute dtype and, with XLA's form, a channel, the
    padded product, the taps' sum and their two cotangents in float32,
    which the kernel's pass keeps in VMEM; nothing kept for the backward
    beside its input; the rung it names: ``[B | C | u]``,
    ``remat.CONV_IN``, which spares ``in_proj``'s product)."""
    width = config.d_model // tensor
    mesh, rows = remat.rows_under_mesh(tokens, seq_len)
    kernel = conv_kernel.path((rows, seq_len, 3 * config.d_model),
                              config.conv_taps, mesh, gated=True) == "kernel"
    return (tokens * width * (2 * 3 * itemsize + (0 if kernel else 4 * 4)),
            0, {remat.CONV_IN: (tokens * 3 * width * itemsize, remat.spared(
                flops=2.0 * tokens * config.d_model * 3 * width))})


def first_call_facts(config, rows: int, seq_len: int) -> Dict[str, Any]:
    return {"shortconv_taps": config.conv_taps,
            "shortconv_width": config.d_model,
            "shortconv_layers": config.rows("C")}


def _gates(bcu):
    """``[B | C | u]`` -> (B, C, u), float32."""
    return (a.astype(jnp.float32) for a in jnp.split(bcu, 3, axis=-1))


@jax.custom_vjp
def gated_conv(bcu, w):
    """bcu: (rows, S, 3 x width), ``[B | C | u]`` as the projection writes
    it; w: (K, width), tap K-1 the position itself.  -> ``C * conv(B * u)``,
    (rows, S, width) in bcu's dtype: float32 throughout, rounded once."""
    B, C, u = _gates(bcu)
    return (C * causal_conv(B * u, w, None)).astype(bcu.dtype)


def _gated_conv_fwd(bcu, w):
    return gated_conv(bcu, w), (bcu, w)


def _gated_conv_bwd(saved, dy):
    bcu, w = saved
    B, C, u = _gates(bcu)
    dy = dy.astype(jnp.float32)
    c, pull = jax.vjp(lambda z, w: causal_conv(z, w, None), B * u, w)
    dz, dw = pull(dy * C)
    return (jnp.concatenate([dz * u, dy * c, dz * B],
                            axis=-1).astype(bcu.dtype), dw)


gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


def mixer(x, blk, config, axes):
    """``x + out_proj(...)``: the layer.  x: (rows, S, D) in the compute
    dtype; ``blk`` one layer of :func:`init_params`; ``axes`` of its
    stack."""
    dt = config.dtype
    with jax.named_scope("shortconv"):
        h = rmsnorm(x, blk["conv_norm"], config.rms_eps).astype(dt)
        bcu = checkpoint_name(dense(h, blk, "in_proj", axes, dt),
                              remat.CONV_IN)
        with jax.named_scope("shortconv_gate"):
            kernel = conv_kernel.engaged(bcu.shape, blk["conv_w"].shape[0],
                                         gated=True)
            y = (conv_kernel.gated if kernel else gated_conv)(
                bcu, blk["conv_w"])
        return x + dense(y, blk, "out_proj", axes, dt)


def layer(config, axes, index: int):
    """Layer ``index`` of the kind as (x, its row of the stack) -> (x,
    None)."""
    return lambda x, blk: (mixer(x, blk, config, axes), None)
