"""Operations and bytes of SDAR's block-diffusion training step on a chip that
holds a share of the experts, from shapes alone (``lib/cost.py``'s rules: no
recomputation counted; norms, the embedding gather, the routing's sort and
gathers and the noise are not matmuls).

Model FLOPs per *trained* token (a row of S ids trains S tokens): the layers
see two positions a token (the noised and the clean copy), the head one.  A
position meets attention's four projections, the router over all published
experts and, of its ``num_experts_per_tok`` experts, those held here: in
expectation ``num_experts_per_tok x held / published`` under an even router
(the rows really routed here are data, and no counter carries them out of
the step).  Attention is counted over the mask's own area, which per head is

    noised x noised   S x Bk            (own block, both directions)
    noised x clean    S x (S - Bk) / 2  (earlier blocks)
    clean x clean     S x (S + Bk) / 2  (own and earlier blocks)
    -------------------------------------------------------------
                      S^2 + S x Bk

so 4 x head_dim FLOPs a pair forward (QK^T and PV), 12 with the backward.

A splash call's cost over such an area is ``lib/cost.py``'s
(``attention_call_cost`` over the kinds the adapter states: the one call over
2S x 2S, and the two calls that cover the same mask, the noised rows S x 2S
and the clean rows S x S, whose areas are the table's first two lines and its
third).
"""

from __future__ import annotations

from typing import Dict


def mask_area(seq_len: int, block_length: int) -> int:
    """Pairs (query, key) one head's mask allows over a row's 2S positions."""
    return seq_len * seq_len + seq_len * block_length


def noised_area(seq_len: int, block_length: int) -> float:
    """Of ``mask_area``, the pairs of the noised copy's S queries (over the
    2S keys: their own block of the noised copy, earlier blocks of the
    clean)."""
    return seq_len * block_length + seq_len * (seq_len - block_length) / 2


def clean_area(seq_len: int, block_length: int) -> float:
    """Of ``mask_area``, the pairs of the clean copy's S queries (over the
    clean copy's S keys)."""
    return seq_len * (seq_len + block_length) / 2


def position_matmul_params(cfg: Dict) -> float:
    """Matrix parameters one position meets in one layer on this chip."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = 2 * D * hd * (H + KV)
    router = D * cfg["num_experts_published"]
    held = cfg["num_experts"] / cfg["num_experts_published"]
    experts = cfg["num_experts_per_tok"] * held \
        * 3 * D * cfg["moe_intermediate_size"]
    return attn + router + experts


def model_flops_per_token(cfg: Dict, seq_len: int) -> float:
    L = cfg["num_hidden_layers"]
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    matmuls = 2 * L * position_matmul_params(cfg) \
        + cfg["vocab_size"] * cfg["hidden_size"]
    attention = 12.0 * L * width * mask_area(seq_len, cfg["block_length"]) \
        / seq_len
    return 6.0 * matmuls + attention
