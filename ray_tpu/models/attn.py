"""The attention layer of a hybrid decoder (``models/hybrid.py``, kind
``*``): ``models/layers.py:attention``, the half ``llama._block`` runs too,
with its parameters and its sizes.  Not a model.

Where ``rope_theta`` is None, without rotary embedding (the recurrent layers
carry the positions); with ``qk_norm`` an RMSNorm with a weight on q and on
k before the rotary pass (``"head"``: over each head's lanes, one weight of
``head_dim`` for all heads; True: over all of a layer's heads, as
``models/llama.py`` has both); with ``attn_gate`` an
output gate before ``wo``, a channel each or with ``"head"`` a scalar a
head; with ``attn_window`` over a causal band of that many keys; with
``rope_rotary`` / ``rope_yarn`` the rotary pass over a head's first lanes
and YaRN's frequencies.  ``models/window.py`` is a second kind over these
functions, with fields of its own in the place of some of these.  A chip may
hold a share of the heads: a smaller
``n_head`` / ``n_kv_head`` (the matrices' columns for the heads held,
``wo``'s rows), with ``n_head_total`` stating how many the model has.  With
``norm_after`` the projections read ``x`` itself and ``attn_norm`` norms
``wo``'s output before the residual add.  The module has the interface
``hybrid.KINDS`` asks of a kind.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import attention, stacked_normal
from ray_tpu.ops import remat
from ray_tpu.ops.attention import dq_partial_bytes

#: the kind reads ``norm_after`` (``models/layers.py:attention``)
NORM_AFTER = True


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
        params["wg"] = norm(jax.random.fold_in(key, 4),
                            (D, _gate_width(config)))
    if config.qk_norm:
        q_width, k_width = _qk_norm_widths(config)
        params["q_norm"] = jnp.ones((n, q_width))
        params["k_norm"] = jnp.ones((n, k_width))
    return params


def _qk_norm_widths(config):
    """The lanes one weight of ``q_norm`` and of ``k_norm`` covers."""
    if config.qk_norm == "head":
        return config.head_dim, config.head_dim
    return (config.n_head * config.head_dim,
            config.n_kv_head * config.head_dim)


def _gate_width(config) -> int:
    """The columns of ``wg``: a scalar a head, or a channel each."""
    return config.n_head * (1 if config.attn_gate == "head"
                            else config.head_dim)


def logical_axes(config) -> Dict[str, Any]:
    L = "layers"
    axes = {"attn_norm": (L, "norm"), "wq": (L, "embed", "heads"),
            "wk": (L, "embed", "heads"), "wv": (L, "embed", "heads"),
            "wo": (L, "heads", "embed")}
    if config.attn_gate:
        axes["wg"] = (L, "embed", "heads")
    if config.qk_norm:
        axes["q_norm"] = axes["k_norm"] = (L, "norm")
    return axes


def matmul_params(config, routed: float) -> int:
    """The matrix entries of one layer that a position meets."""
    return config.d_model * (config.head_dim * (
        2 * config.n_head + 2 * config.n_kv_head)
        + (_gate_width(config) if config.attn_gate else 0))


def num_params(config) -> int:
    """Of one layer, its norm included."""
    return matmul_params(config, 0) + config.d_model \
        + (sum(_qk_norm_widths(config)) if config.qk_norm else 0)


def mixer_flops(config, seq_len: int) -> float:
    """Forward FLOPs a position beside the matrices: QK^T and PV, 4 x heads
    x head_dim a (query, key) pair the mask allows: causal, half the square,
    ``S / 2`` pairs a position; a band of ``w`` keys ``w - w (w - 1) / (2
    S)``, no more than a kernel has to compute."""
    w = min(config.attn_window, seq_len)
    pairs = w - w * (w - 1) / (2 * seq_len) if w else seq_len / 2
    return 4.0 * config.n_head * config.head_dim * pairs


def layer_bytes(config, tokens: int, seq_len: int, tensor: int,
                itemsize: int):
    """For ``hybrid._layer_sizes``, a chip's bytes of one layer over
    ``tokens`` positions with the heads cut ``tensor`` ways: (its working
    set: three arrays as wide as the heads in the kernel's layout (a head's
    lanes padded to the chip's tiles of 128), the gate at its own width and the
    dq partials of the splash call's fused backward, one of q's size for
    every kv block of the row, ``attention.dq_partial_bytes``; what
    it keeps for the backward beside its input: the kernel's output and
    log-sum-exp; the rung it names: q, k and v, which spare their three
    products and, where the layer has them, the QK-norm's and the rotary
    passes over q and k)."""
    width = config.n_head * config.head_dim // tensor
    qkv_width = (config.n_head + 2 * config.n_kv_head) * config.head_dim \
        // tensor
    gate = _gate_width(config) // tensor if config.attn_gate else 0
    padded = config.n_head // tensor * -(-config.head_dim // 128) * 128
    turned = (qkv_width + width) // 2  # q and k
    passes = 2 * bool(config.qk_norm) + 2 * (config.rope_theta is not None)
    return (tokens * (3 * padded + gate) * itemsize + dq_partial_bytes(
        tokens, seq_len, config.n_head // tensor, config.head_dim, itemsize,
        config.attn_impl, config.attn_window),
            tokens * (width * itemsize + config.n_head // tensor * 4),
            {remat.QKV: (tokens * qkv_width * itemsize, remat.spared(
                flops=2.0 * tokens * config.d_model * qkv_width,
                moved=tokens * passes * turned * itemsize))})


def first_call_facts(config, rows: int, seq_len: int) -> Dict[str, Any]:
    facts = {"attn_positions": seq_len, "heads_held": config.n_head,
             "heads_total": config.n_head_total or config.n_head,
             "attn_gate": config.attn_gate}
    if config.qk_norm:
        facts["qk_norm"] = config.qk_norm
    if config.rope_rotary is not None:
        facts["rope_rotary_lanes"] = config.rope_rotary
    if config.rope_yarn is not None:
        facts["rope_yarn_factor"] = config.rope_yarn.factor
    return facts


def layer(config, axes, index: int):
    """Layer ``index`` of the kind as (x, its row of the stack) -> (x,
    None)."""
    return lambda x, blk: (attention(x, blk, config, axes), None)
