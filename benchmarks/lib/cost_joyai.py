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

**A splash call at two head dimensions**: the forward is QK^T over the q.k
head and PV over the v head, ``2 x (qk + v)`` FLOPs a pair; the fused
backward recomputes QK^T and makes dV, dP, dQ and dK: three products over
the q.k head and two over the v head, ``2 x (3 qk + 2 v)`` a pair; both over
the causal half.  Bytes: one read of each input and one write of each output
in the kernel's dtype, q and k (and dq, dk) at the q.k head, v and o (and do,
dv) at the v head: the model's own 192 and 128, whatever an implementation
pads.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmarks.lib import cost


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


def attention_call_cost(kind: str, cfg: Dict, batch: int, seq: int,
                        itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one causal splash call over ``batch`` rows of
    ``seq`` positions; ``kind`` is ``fwd`` or ``bwd``."""
    qk, dv = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    pairs = batch * cfg["num_attention_heads"] * seq * seq * 0.5
    tensor = batch * cfg["num_attention_heads"] * seq * itemsize
    if kind == "fwd":    # q k v -> o
        return 2.0 * (qk + dv) * pairs, (2 * qk + 2 * dv) * tensor
    if kind == "bwd":    # q k v o do -> dq dk dv
        return 2.0 * (3 * qk + 2 * dv) * pairs, (4 * qk + 4 * dv) * tensor
    raise ValueError(f"attention call kind {kind!r} (use fwd|bwd)")


def attention_least_time(kind: str, cfg: Dict, batch: int, seq: int,
                         peak_flops: float, peak_bw: float):
    return cost.least_time(*attention_call_cost(kind, cfg, batch, seq),
                           peak_flops, peak_bw)
