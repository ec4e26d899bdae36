"""The attention layer of a hybrid decoder (``models/hybrid.py``, kind
``*``): ``models/layers.py:attention``, the half ``llama._block`` runs too,
with its parameters and its sizes.  Not a model.

Here without QK-norm and, where ``rope_theta`` is None, without rotary
embedding (the recurrent layers carry the positions); with ``attn_gate`` an
output gate before ``wo``.  A chip may hold a share of the heads: a smaller
``n_head`` / ``n_kv_head`` (the matrices' columns for the heads held,
``wo``'s rows), with ``n_head_total`` stating how many the model has.  The
module has the interface ``hybrid.KINDS`` asks of a kind.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import attention, stacked_normal
from ray_tpu.ops import remat


def init_params(config, key, n: int, out_std: float) -> Dict[str, Any]:
    """``n`` layers stacked on a leading axis.  Matrices normal(0.02),
    ``wo`` normal(``out_std``), the norm ones."""
    D, H, KV, hd = (config.d_model, config.n_head, config.n_kv_head,
                    config.head_dim)
    ks = jax.random.split(key, 4)
    norm = partial(stacked_normal, n)

    params = {
        "attn_norm": jnp.ones((n, D)),
        "wq": norm(ks[0], (D, H * hd)),
        "wk": norm(ks[1], (D, KV * hd)),
        "wv": norm(ks[2], (D, KV * hd)),
        "wo": norm(ks[3], (H * hd, D), out_std),
    }
    if config.attn_gate:
        params["wg"] = norm(jax.random.fold_in(key, 4), (D, H * hd))
    return params


def logical_axes(config) -> Dict[str, Any]:
    L = "layers"
    axes = {"attn_norm": (L, "norm"), "wq": (L, "embed", "heads"),
            "wk": (L, "embed", "heads"), "wv": (L, "embed", "heads"),
            "wo": (L, "heads", "embed")}
    if config.attn_gate:
        axes["wg"] = (L, "embed", "heads")
    return axes


def matmul_params(config, routed: float) -> int:
    """The matrix entries of one layer that a position meets."""
    return config.d_model * config.head_dim * (
        (3 if config.attn_gate else 2) * config.n_head + 2 * config.n_kv_head)


def num_params(config) -> int:
    """Of one layer, its pre-norm included."""
    return matmul_params(config, 0) + config.d_model


def mixer_flops(config, seq_len: int) -> float:
    """Forward FLOPs a position beside the matrices: QK^T and PV, causal."""
    return 2.0 * config.n_head * config.head_dim * seq_len


def layer_bytes(config, tokens: int, seq_len: int, tensor: int,
                itemsize: int):
    """For ``hybrid._layer_sizes``, a chip's bytes of one layer over
    ``tokens`` positions with the heads cut ``tensor`` ways: (its working
    set: six arrays as wide as the heads, seven with the gate; what it keeps
    for the backward beside its input: the kernel's output and log-sum-exp;
    the ladder's candidates it names: q, k and v)."""
    width = config.n_head * config.head_dim // tensor
    qkv_width = (config.n_head + 2 * config.n_kv_head) * config.head_dim \
        // tensor
    return ((7 if config.attn_gate else 6) * tokens * width * itemsize,
            tokens * (width * itemsize + config.n_head // tensor * 4),
            {remat.QKV: tokens * qkv_width * itemsize})


def first_call_facts(config, rows: int, seq_len: int) -> Dict[str, Any]:
    return {"attn_positions": seq_len, "heads_held": config.n_head,
            "heads_total": config.n_head_total or config.n_head,
            "attn_gate": config.attn_gate}


def layer(config, axes, index: int):
    """Layer ``index`` of the kind as (x, its row of the stack) -> (x,
    None)."""
    return lambda x, blk: (attention(x, blk, config, axes), None)
