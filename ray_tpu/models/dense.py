"""The dense feed-forward layer of a hybrid decoder (``models/hybrid.py``,
kind ``D``): ``models/layers.py:feed_forward`` without experts, the SwiGLU
MLP ``x + down(silu(gate h) * up h)`` over ``h = norm(x)`` that
``llama._block`` runs too (with ``norm_after`` ``x + norm(down(silu(gate
x) * up x))``), with its parameters and its sizes: the leading layers of a
model whose later layers hold experts (``first_k_dense_replace``), or every
layer's feed-forward part in a model without experts.  Not a model.  ``gate`` and ``up`` are named for
``ops/remat.py``.  The module has the interface ``hybrid.KINDS`` asks of a
kind.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.layers import (feed_forward, feed_forward_branch,
                                   stacked_normal)
from ray_tpu.ops import remat

#: the kind reads ``norm_after`` (``models/layers.py:feed_forward``)
NORM_AFTER = True


def init_params(config, key, n: int, out_std: float) -> Dict[str, Any]:
    """``n`` layers stacked on a leading axis.  Matrices normal(0.02),
    ``w_down`` normal(``out_std``), the norm ones."""
    D, F = config.d_model, config.dense_width
    ks = jax.random.split(key, 3)
    norm = partial(stacked_normal, n)
    return {"mlp_norm": jnp.ones((n, D)), "w_gate": norm(ks[0], (D, F)),
            "w_up": norm(ks[1], (D, F)),
            "w_down": norm(ks[2], (F, D), out_std)}


def logical_axes(config) -> Dict[str, Any]:
    L = "layers"
    return {"mlp_norm": (L, "norm"), "w_gate": (L, "embed", "mlp"),
            "w_up": (L, "embed", "mlp"), "w_down": (L, "mlp", "embed")}


def matmul_params(config, routed: float) -> int:
    """The matrix entries of one layer that a position meets."""
    return 3 * config.d_model * config.dense_width


def num_params(config) -> int:
    """Of one layer, its norm included."""
    return matmul_params(config, 0) + config.d_model


def mixer_flops(config, seq_len: int) -> float:
    """Nothing beside the matrices."""
    return 0.0


def layer_bytes(config, tokens: int, seq_len: int, tensor: int,
                itemsize: int):
    """For ``hybrid._layer_sizes``, a chip's bytes of one layer over
    ``tokens`` positions with the width cut ``tensor`` ways: (its working
    set: the gate and up products, their activation and the three
    cotangents; nothing kept for the backward beside its input; the rung it
    names: the gate and up products, which spare those two products)."""
    width = config.dense_width // tensor
    return (6 * tokens * width * itemsize, 0,
            {remat.GATE_UP: (2 * tokens * width * itemsize, remat.spared(
                flops=2.0 * tokens * config.d_model * 2 * width))})


def first_call_facts(config, rows: int, seq_len: int) -> Dict[str, Any]:
    return {"dense_width": config.dense_width}


def layer(config, axes, index: int):
    """Layer ``index`` of the kind as (x, its row of the stack) -> (x,
    None)."""
    return lambda x, blk: (feed_forward(x, blk, config, axes)[0], None)


def branch(config, axes, index: int):
    """Layer ``index`` without its residual add, as (u, its row of the
    stack) -> (the MLP over ``norm(u)``, None)."""
    return lambda u, blk: feed_forward_branch(u, blk, config, axes)
