"""Operations and bytes of LFM2-8B-A1B's training step on a chip that holds a
share of the routed experts and of the vocabulary, from shapes alone
(``lib/cost.py``'s rules: no recomputation counted in the model's FLOPs;
norms, the embedding gather, the rotary pass and the routing's sort and
gathers are not matmuls).

Model FLOPs per trained token: 6 x the matrix parameters a position meets (a
convolution layer's two projections, hidden x 3 hidden and hidden x hidden;
an attention layer's four; the leading layer's dense MLP; every other
layer's router over all published outputs and, of its
``num_experts_per_tok`` routed experts, those held here, in expectation
``num_experts_per_tok x held / published`` under an even router; the tied
head once: the embedding is a gather) plus causal attention at half the
square plus 3 x what a convolution layer's gate pass computes forward: a
multiply-add a tap and a multiplication a gate, a channel.  The taps are
counted as what they are, ``2 K`` FLOPs a channel, and not as a product with
a banded S x S matrix, which no unit computes.

**The gate pass** (``ray_tpu/models/shortconv.py:gated_conv``; the scope
``shortconv_gate``): ``C * conv(B * u)`` over ``[B | C | u]``, hidden wide
each.  What no implementation avoids moving, a position, in the compute
dtype: forward it reads the three and writes the result (3 + 1 values a
channel); backward it reads the three and the result's cotangent and writes
the three's (4 + 3); where the layer's checkpoint runs the forward again, the
forward's once more.  The taps (K x hidden) are noise beside them.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks.lib import cost


def layers_run(cfg: Dict) -> List[int]:
    """The published indices of the layers a cut of ``num_hidden_layers``
    runs: the leading dense layers counted once (layer 0), then the layers
    from ``num_dense_layers`` on.  At the published depth, every layer."""
    depth, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    if depth >= len(cfg["layer_types"]):
        return list(range(depth))
    return [0] + list(range(dense, dense + depth - 1))


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """How many of the layers run are convolution, attention, dense-MLP and
    expert layers."""
    run = layers_run(cfg)
    conv = sum(cfg["layer_types"][i] == "conv" for i in run)
    dense = sum(i < cfg["num_dense_layers"] for i in run)
    return {"conv": conv, "attn": len(run) - conv, "dense": dense,
            "experts": len(run) - dense}


def layer_matmul_params(cfg: Dict) -> Dict[str, float]:
    """Matrix parameters one position meets in a layer's part of each
    kind."""
    D = cfg["hidden_size"]
    hd = D // cfg["num_attention_heads"]
    held = cfg["num_experts"] / cfg["num_experts_published"]
    return {
        "conv": 4 * D * D,
        "attn": 2 * D * hd * (cfg["num_attention_heads"]
                              + cfg["num_key_value_heads"]),
        "dense": 3 * D * cfg["intermediate_size"],
        "experts": D * cfg["num_experts_published"]
        + cfg["num_experts_per_tok"] * held
        * 3 * D * cfg["moe_intermediate_size"],
    }


def params_held(cfg: Dict) -> int:
    """Every parameter that exists on this chip: the matrices (of the routed
    experts the held ones), the taps, the norms (a layer's two, an attention
    layer's two over a head) and the embedding, which is the head."""
    D = cfg["hidden_size"]
    hd = D // cfg["num_attention_heads"]
    n = layer_counts(cfg)
    met = layer_matmul_params(dict(
        cfg, num_experts_per_tok=cfg["num_experts_published"]))
    return int(n["conv"] * (met["conv"] + (cfg["conv_L_cache"] + 1) * D)
               + n["attn"] * (met["attn"] + D + 2 * hd)
               + n["dense"] * (met["dense"] + D)
               + n["experts"] * (met["experts"] + D)
               + cfg["vocab_size"] * D + D)


def gate_flops_per_position(cfg: Dict) -> float:
    """Forward FLOPs of one convolution layer's gate pass a position."""
    return (2.0 * cfg["conv_L_cache"] + 2.0) * cfg["hidden_size"]


def model_flops_per_token(cfg: Dict, seq_len: int) -> float:
    D = cfg["hidden_size"]
    n, met = layer_counts(cfg), layer_matmul_params(cfg)
    matmuls = sum(n[kind] * met[kind] for kind in n) + cfg["vocab_size"] * D
    attention = 6.0 * n["attn"] * seq_len * D
    return 6.0 * matmuls + attention \
        + 3.0 * n["conv"] * gate_flops_per_position(cfg)


def gate_step_cost(cfg: Dict, tokens: int, recomputed: bool,
                   itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of every convolution layer's gate pass for ``tokens``
    positions of a step: forward, backward, and with ``recomputed`` the
    forward once more."""
    D, layers = cfg["hidden_size"], layer_counts(cfg)["conv"]
    forwards = 2 if recomputed else 1
    values = forwards * (3 + 1) + (4 + 3)
    flops = (forwards + 2) * gate_flops_per_position(cfg)
    return layers * tokens * flops, \
        float(layers * tokens * values * D * itemsize)


def gate_least_time(cfg: Dict, tokens: int, recomputed: bool,
                    peak_flops: float, peak_bw: float) -> Tuple[float, str]:
    return cost.least_time(*gate_step_cost(cfg, tokens, recomputed),
                           peak_flops, peak_bw)
