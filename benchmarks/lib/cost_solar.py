"""Operations and bytes of Solar-Open2's training step on a chip that holds
a share of the heads and of the routed experts, from shapes alone
(``lib/cost.py``'s rules: no recomputation counted in the model's FLOPs;
norms, the embedding gather, the convolutions, the gates' elementwise parts
and the routing's sort and gathers are not matmuls).

Model FLOPs per trained token: 6 x the matrix parameters a position meets (a
KDA layer's q, k, v and o for the heads held, its two low-rank gates and
beta; an attention layer's q, k, v, o and output gate for the heads held;
every layer's router over all published outputs, its three-matrix shared
expert and, of its ``num_experts_per_tok`` routed experts, those held here,
in expectation ``num_experts_per_tok x held / published`` under an even
router; the head once) plus causal attention at half the square and the
delta-rule scan.

**The scan** (``ray_tpu/ops/kda.py``; H heads of d, chunks of C), a position
a head, forward, in multiply-adds:

    A = K K^T and B = Q K^T with the decay inside    C x d / 2 each (causal)
    T [V | Kbar]                                     C x 2d / 2 (triangular)
    B U                                              C x d / 2 (causal)
    (T Kbar) S_0,  Qbar S_0,  Kend^T U               d x d each

about 139 k FLOPs at C = 64, d = 128; the triangular system's solution is
not counted (an implementation may get it as it likes).  2 FLOPs a
multiply-add forward, 4 more backward.  What the scan cannot avoid moving,
each way: q, k, v and o (H x d a position each, the compute dtype), g (H x
d, float32), beta (H, float32), and the chunk states out and in (H x d x d
float32 a chunk, twice).  The backward reads the same again and writes
their cotangents.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmarks.lib import cost


def layers(cfg: Dict) -> Tuple[int, int]:
    """(KDA layers, softmax layers) of the configuration's depth."""
    softmax = sum(i in cfg["gqa_layers"]
                  for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - softmax, softmax


def layer_matmul_params(cfg: Dict) -> Dict[str, float]:
    """Matrix parameters one position meets in a KDA mixer, a softmax mixer
    and a layer's expert part."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    linear = cfg["linear_attn_config"]
    inner = linear["num_heads"] * linear["head_dim"]
    rank = linear["head_dim"]  # kda_use_full_proj false
    held = cfg["n_routed_experts"] / cfg["n_routed_experts_published"]
    width = cfg["moe_intermediate_size"]
    return {
        "kda": 4 * D * inner + 2 * (D * rank + rank * inner)
        + D * linear["num_heads"],
        "attn": D * hd * (3 * cfg["num_attention_heads"]
                          + 2 * cfg["num_key_value_heads"]),
        "experts": D * cfg["n_routed_experts_published"]
        + 3 * D * width * (cfg["n_shared_experts"]
                           + cfg["num_experts_per_tok"] * held),
    }


def scan_flops_per_position(cfg: Dict, seq_len: int) -> float:
    """Forward FLOPs of one KDA layer's scan a position."""
    linear = cfg["linear_attn_config"]
    d, C = linear["head_dim"], min(cfg["kda_chunk"], seq_len)
    return 2.0 * linear["num_heads"] * (2.5 * C * d + 3 * d * d)


def model_flops_per_token(cfg: Dict, seq_len: int) -> float:
    kda, softmax = layers(cfg)
    met = layer_matmul_params(cfg)
    matmuls = kda * met["kda"] + softmax * met["attn"] \
        + cfg["num_hidden_layers"] * met["experts"] \
        + cfg["vocab_size"] * cfg["hidden_size"]
    attention = 6.0 * softmax * seq_len * cfg["num_attention_heads"] \
        * cfg["head_dim"]
    return 6.0 * matmuls + attention \
        + 3.0 * kda * scan_flops_per_position(cfg, seq_len)


def scan_step_cost(cfg: Dict, tokens: int, seq_len: int, passes: float,
                   itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of every KDA layer's scan for ``tokens`` positions of
    a step.  ``passes`` counts forward passes: 1 forward, 2 more for the
    backward, 1 more where the layer's checkpoint runs the forward again."""
    linear = cfg["linear_attn_config"]
    H, d = linear["num_heads"], linear["head_dim"]
    C = min(cfg["kda_chunk"], seq_len)
    n = layers(cfg)[0]
    flops = scan_flops_per_position(cfg, seq_len) * tokens
    a_position = H * (4 * d * itemsize + d * 4 + 4)
    states = 2 * H * d * d * 4 / C
    return n * passes * flops, n * passes * tokens * (a_position + states)


def scan_least_time(cfg: Dict, tokens: int, seq_len: int, passes: float,
                    peak_flops: float, peak_bw: float) -> Tuple[float, str]:
    return cost.least_time(*scan_step_cost(cfg, tokens, seq_len, passes),
                           peak_flops, peak_bw)
