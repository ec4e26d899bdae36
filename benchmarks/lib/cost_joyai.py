"""Operations and bytes of JoyAI-LLM-Flash's training step on a chip that
holds a share of the routed experts and of the vocabulary, from shapes alone
(``lib/cost.py``'s rules: no recomputation counted in the model's FLOPs;
norms, the embedding gathers, the rotary passes, the gates' elementwise
parts and the routing's sort and gathers are not matmuls).

Model FLOPs per trained token: 6 x the matrix parameters a position meets
(every layer's latent attention: the two down-projections, the two
up-projections and ``wo``, every head held; the leading layers' dense MLP;
every other layer's router over all published outputs, its three-matrix
shared expert and, of its ``num_experts_per_tok`` routed experts, those held
here, in expectation ``num_experts_per_tok x held / published`` under an even
router; the head once) plus causal attention at half the square, over a q.k
head of ``qk_head_dim`` and a v head of ``v_head_dim``.  **The prediction
module** adds, for each of its ``num_nextn_predict_layers``: ``w_eh`` (2 x
hidden x hidden), one more layer of latent attention and experts, and one
more pass through the head.

A splash call's cost at the two head dimensions is ``lib/cost.py``'s
(``attention_call_cost`` over the kind the adapter states).
"""

from __future__ import annotations

from typing import Dict, Tuple


def layers(cfg: Dict) -> Tuple[int, int, int]:
    """(latent-attention layers, dense MLP layers, expert layers), the
    prediction modules' among them."""
    depth, more = cfg["num_hidden_layers"], cfg["num_nextn_predict_layers"]
    dense = min(cfg["first_k_dense_replace"], depth)
    return depth + more, dense, depth - dense + more


def layer_matmul_params(cfg: Dict) -> Dict[str, float]:
    """Matrix parameters one position meets in a latent-attention mixer, a
    dense MLP and a layer's expert part."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qk, dv = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    held = cfg["n_routed_experts"] / cfg["n_routed_experts_published"]
    return {
        "mla": D * cfg["q_lora_rank"] + cfg["q_lora_rank"] * H * qk
        + D * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
        + cfg["kv_lora_rank"] * H * (cfg["qk_nope_head_dim"] + dv)
        + H * dv * D,
        "dense": 3 * D * cfg["intermediate_size"],
        "experts": D * cfg["n_routed_experts_published"]
        + 3 * D * cfg["moe_intermediate_size"] * (
            cfg["n_shared_experts"] + cfg["num_experts_per_tok"] * held),
    }


def params_held(cfg: Dict) -> int:
    """Every parameter that exists on this chip: the matrices (of the routed
    experts the held ones), the norms, the embedding and the head."""
    D = cfg["hidden_size"]
    mla, dense, experts = layers(cfg)
    met = layer_matmul_params(dict(
        cfg, num_experts_per_tok=cfg["n_routed_experts_published"]))
    more = cfg["num_nextn_predict_layers"]
    return int(mla * (met["mla"] + D + cfg["q_lora_rank"]
                      + cfg["kv_lora_rank"])
               + dense * (met["dense"] + D) + experts * (met["experts"] + D)
               + 2 * cfg["vocab_size"] * D + D
               + more * (2 * D * D + 3 * D))


def model_flops_per_token(cfg: Dict, seq_len: int) -> float:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    mla, dense, experts = layers(cfg)
    met = layer_matmul_params(cfg)
    more = cfg["num_nextn_predict_layers"]
    matmuls = mla * met["mla"] + dense * met["dense"] \
        + experts * met["experts"] + (1 + more) * cfg["vocab_size"] * D \
        + more * 2 * D * D
    attention = 3.0 * mla * seq_len * H * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    return 6.0 * matmuls + attention
