"""Operations and bytes of a dropless mixture-of-experts decoder, from shapes
alone (``lib/cost.py``'s rules: no recomputation counted, norms, the
embedding gather and the routing's sort and gathers are not matmuls).

Model FLOPs per token: 6 x the matmul parameters a token meets (attention's
four projections, the router, its own ``num_experts_per_tok`` experts of the
``num_experts``, the LM head once) plus causal attention.

The expert layer's grouped products: a SwiGLU expert has three matrices of
hidden x width, and training multiplies each three times (forward, dx, dW),
so a layer is nine grouped products of ``2 x rows x hidden x width`` FLOPs
with rows = tokens x experts per token.  Each product reads every expert's
matrix once and its rows in and out once, in the compute dtype (for dW the
"out" is the float32-accumulated matrix, counted at the compute dtype's
size too: the least an implementation could write).
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmarks.lib import cost


def olmoe_matmul_params(cfg: Dict) -> int:
    """Published-key config -> matmul parameters one token meets."""
    D, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = D // H
    attn = D * H * hd + 2 * D * KV * hd + H * hd * D
    router = D * cfg["num_experts"]
    experts = cfg["num_experts_per_tok"] * 3 * D * cfg["intermediate_size"]
    return L * (attn + router + experts) + V * D


def model_flops_per_token(cfg: Dict, seq_len: int) -> float:
    return cost.model_flops_per_token(
        olmoe_matmul_params(cfg), cfg["num_hidden_layers"],
        cfg["hidden_size"], seq_len)


def grouped_product_cost(rows: int, groups: int, hidden: int, width: int,
                         itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one grouped product over ``rows`` rows in ``groups``
    groups between ``hidden`` and ``width`` (either direction, or the
    per-group weight gradient: the three have the same counts)."""
    flops = 2.0 * rows * hidden * width
    nbytes = float(itemsize) * (groups * hidden * width
                                + rows * hidden + rows * width)
    return flops, nbytes


def expert_layer_cost(cfg: Dict, tokens: int, itemsize: int = 2
                      ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the nine grouped products of every layer for
    ``tokens`` tokens on one chip: ``18 x rows x hidden x width`` FLOPs a
    layer."""
    flops, nbytes = grouped_product_cost(
        tokens * cfg["num_experts_per_tok"], cfg["num_experts"],
        cfg["hidden_size"], cfg["intermediate_size"], itemsize)
    n = 9 * cfg["num_hidden_layers"]
    return n * flops, n * nbytes
