"""The expert layer of a hybrid decoder (``models/hybrid.py``, kind ``E``):
``models/layers.py:feed_forward`` over ``models/moe.py``, with its parameters
and its sizes.  Not a model.

Sigmoid (or softmax) scores, a selection bias that picks the experts and
does not weigh them, renormalised weights times ``routed_scaling``, experts
of two matrices with relu^2 (or, with ``gated_experts``, of three: SwiGLU), a
shared expert of the same make beside them; ``experts_held`` says which
experts this chip holds.  The held experts' products are recomputed inside
their own backward (``moe._held_move``); the shared expert names its up (and
gate) product for ``ops/remat.py``, and what the router decided is named and
always kept (``remat.ROUTING``).

**The selection bias** is no parameter: the published recipe moves it by the
load, outside the gradient.  Here it is a constant of the configuration,
drawn a layer from ``router_bias_seed`` (numpy, when the step is traced),
so that the mechanism is not a no-op at zero; no leaf holds it, so the
optimizer cannot touch it.  The module has the interface ``hybrid.KINDS``
asks of a kind.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import moe
from ray_tpu.models.layers import (feed_forward, feed_forward_branch,
                                   stacked_normal)
from ray_tpu.ops import remat

#: what ``expert_activation`` may name
ACTIVATIONS = {"relu2": moe.relu2, "silu": jax.nn.silu}


def router_bias(config, layer: int) -> Optional[np.ndarray]:
    """The selection bias of the ``layer``-th expert layer, (E,) float32: a
    function of the configuration, the same on every trace."""
    if not config.router_bias_std:
        return None
    rng = np.random.default_rng([config.router_bias_seed, layer])
    return (rng.standard_normal(config.n_experts)
            * config.router_bias_std).astype(np.float32)


def init_params(config, key, n: int, out_std: float) -> Dict[str, Any]:
    """``n`` layers stacked on a leading axis.  Matrices normal(0.02), the
    experts' and the shared expert's down normal(``out_std``), the norm
    ones; of the routed experts the held ones."""
    D, held, F, Fs = (config.d_model, len(config.held), config.d_ff,
                      config.shared_width)
    ks = jax.random.split(key, 5)
    norm = partial(stacked_normal, n)

    params = {
        "mlp_norm": jnp.ones((n, D)),
        "router": norm(ks[0], (D, config.n_experts)),
        "w_up": norm(ks[1], (held, D, F)),
        "w_down": norm(ks[2], (held, F, D), out_std),
    }
    if Fs:
        params["shared_up"] = norm(ks[3], (D, Fs))
        params["shared_down"] = norm(ks[4], (Fs, D), out_std)
    if config.gated_experts:
        gates = jax.random.split(jax.random.fold_in(key, 5))
        params["w_gate"] = norm(gates[0], (held, D, F))
        if Fs:
            params["shared_gate"] = norm(gates[1], (D, Fs))
    return params


def logical_axes(config) -> Dict[str, Any]:
    L = "layers"
    axes = {"mlp_norm": (L, "norm"), "router": (L, "embed", None),
            "w_up": (L, "expert", "embed", "mlp"),
            "w_down": (L, "expert", "mlp", "embed")}
    if config.shared_width:
        axes["shared_up"] = (L, "embed", "mlp")
        axes["shared_down"] = (L, "mlp", "embed")
    if config.gated_experts:
        axes["w_gate"] = (L, "expert", "embed", "mlp")
        if config.shared_width:
            axes["shared_gate"] = (L, "embed", "mlp")
    return axes


def _matrices(config) -> int:
    """Of one expert, and of the shared one."""
    return 3 if config.gated_experts else 2


def matmul_params(config, routed: float) -> float:
    """The matrix entries of one layer that a position meets, with ``routed``
    of its routed experts counted: the router, the shared expert and those."""
    return config.d_model * config.n_experts + _matrices(config) \
        * config.d_model * (config.shared_width + routed * config.d_ff)


def num_params(config) -> int:
    """Of one layer that exist here, its pre-norm included: of the routed
    experts the held ones."""
    return matmul_params(config, len(config.held)) + config.d_model


def mixer_flops(config, seq_len: int) -> float:
    """Nothing beside the matrices."""
    return 0.0


def layer_bytes(config, tokens: int, seq_len: int, tensor: int,
                itemsize: int):
    """For ``hybrid._layer_sizes``, a chip's bytes of one layer over
    ``tokens`` positions with the shared expert's width cut ``tensor`` ways:
    (its working set: the shared expert's products, their activation and the
    cotangents, and four copies of the rows the layer moves at once, each
    with the routed experts' products and their cotangents beside it: every
    (position, expert) pair where all experts are held, one window of the
    held run (``moe.window_rows``) where the chip holds a share; what stands
    from its forward to its backward beside its input: the held experts'
    matrices in the compute dtype, which the compiled step casts once; what
    it names: of the rungs the shared expert's up, and gate, product (and,
    every expert held, the routed experts' over every pair; a share's run
    inside its loop keeps nothing past its pass), which spare those
    products, and the routing, which no rule decides on)."""
    shared, m = config.shared_width // tensor, _matrices(config)
    pairs = tokens * config.experts_per_token
    whole = len(config.held) == config.n_experts
    moved = pairs if whole else moe.window_rows(pairs)
    kept_wide = (m - 1) * (shared * tokens + config.d_ff * pairs * whole)
    named = {remat.ROUTING: (moe.routing_bytes(
        tokens, config.n_experts, config.experts_per_token), 0.0)}
    if kept_wide:
        named = {remat.GATE_UP: (kept_wide * itemsize, remat.spared(
            flops=2.0 * config.d_model * kept_wide)), **named}
    return (tokens * 3 * m * shared * itemsize
            + moved * (4 * config.d_model + 2 * m * config.d_ff) * itemsize,
            len(config.held) * m * config.d_model * config.d_ff * itemsize,
            named)


def first_call_facts(config, rows: int, seq_len: int) -> Dict[str, Any]:
    return {"experts_held": len(config.held),
            "experts_total": config.n_experts,
            "router_scoring": config.router_scoring}


def _expert_layer(config, index: int) -> Dict[str, Any]:
    """What ``moe.moe_mlp`` is handed for the ``index``-th expert layer."""
    return dict(scoring=config.router_scoring,
                bias=router_bias(config, index), scale=config.routed_scaling,
                activation=ACTIVATIONS[config.expert_activation])


def layer(config, axes, index: int):
    """Layer ``index`` of the kind as (x, its row of the stack) -> (x, the
    layer's counts: ``moe.moe_mlp``'s)."""
    def experts(x, blk):
        x, (_, counts) = feed_forward(x, blk, config, axes,
                                      **_expert_layer(config, index))
        return x, counts

    return experts


def branch(config, axes, index: int):
    """Layer ``index`` without its residual add, as (u, its row of the
    stack) -> (the experts' output over ``norm(u)``, the layer's counts)."""
    def experts(u, blk):
        y, (_, counts) = feed_forward_branch(u, blk, config, axes,
                                             **_expert_layer(config, index))
        return y, counts

    return experts
