"""Operations and bytes of Nemotron-3-Nano's training step on a chip that
holds a share of the routed experts, from shapes alone (``lib/cost.py``'s
rules: no recomputation counted in the model's FLOPs; norms, the embedding
gather, the convolution, the gate and the routing's sort and gathers are not
matmuls).

Model FLOPs per trained token: 6 x the matrix parameters a position meets
(a Mamba layer's two projections; an attention layer's four; an expert
layer's router over all published outputs, its shared expert and, of its
``num_experts_per_tok`` routed experts, those held here, in expectation
``num_experts_per_tok x held / published`` under an even router; the head
once) plus causal attention at half the square and the state-space scan.

**The scan** (``ray_tpu/ops/ssd.py``; H heads of P, G groups, state N, chunks
of Q) is four families of products a chunk, counted a position at the causal
half where a mask halves them:

    C B^T                a group   Q x N / 2  multiply-adds a position
    (L o C B^T)(delta x) a head    Q x P / 2
    the chunk's state    a head    P x N
    C x incoming state   a head    P x N

2 FLOPs a multiply-add forward, 4 more backward.  What the scan cannot avoid
moving, each way: x and y (H x P a position), B and C (G x N each), delta (H,
float32), and the chunk states out and in (H x P x N float32 a chunk, twice).
The backward reads the same again and writes their cotangents.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmarks.lib import cost

KINDS = "M*E"


def layer_matmul_params(cfg: Dict) -> Dict[str, float]:
    """Matrix parameters one position meets in one layer of each kind."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner = H * P
    in_proj = 2 * inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"] + H
    held = cfg["n_routed_experts"] / cfg["n_routed_experts_published"]
    shared = cfg["n_shared_experts"] \
        * cfg["moe_shared_expert_intermediate_size"]
    return {
        "M": D * in_proj + inner * D,
        "*": 2 * D * hd * (cfg["num_attention_heads"]
                           + cfg["num_key_value_heads"]),
        "E": D * cfg["n_routed_experts_published"] + 2 * D * shared
        + cfg["num_experts_per_tok"] * held
        * 2 * D * cfg["moe_intermediate_size"],
    }


def scan_flops_per_position(cfg: Dict, seq_len: int) -> float:
    """Forward FLOPs of one Mamba layer's scan a position."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    Q = min(cfg["chunk_size"], seq_len)
    return 2.0 * (G * Q * N / 2 + H * Q * P / 2 + 2 * H * P * N)


def model_flops_per_token(cfg: Dict, seq_len: int) -> float:
    pattern = cfg["hybrid_override_pattern"]
    met = layer_matmul_params(cfg)
    matmuls = sum(met[kind] for kind in pattern) \
        + cfg["vocab_size"] * cfg["hidden_size"]
    attention = 6.0 * pattern.count("*") * seq_len \
        * cfg["num_attention_heads"] * cfg["head_dim"]
    scan = 3.0 * pattern.count("M") * scan_flops_per_position(cfg, seq_len)
    return 6.0 * matmuls + attention + scan


def scan_step_cost(cfg: Dict, tokens: int, seq_len: int, passes: float,
                   itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of every Mamba layer's scan for ``tokens`` positions
    of a step.  ``passes`` counts forward passes: 1 forward, 2 more for the
    backward, 1 more where the layer's checkpoint runs the forward again."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    Q = min(cfg["chunk_size"], seq_len)
    layers = cfg["hybrid_override_pattern"].count("M")
    flops = scan_flops_per_position(cfg, seq_len) * tokens
    a_position = (2 * H * P + 2 * G * N) * itemsize + H * 4
    states = 2 * H * P * N * 4 / Q
    return layers * passes * flops, \
        layers * passes * tokens * (a_position + states)


def scan_least_time(cfg: Dict, tokens: int, seq_len: int, passes: float,
                    peak_flops: float, peak_bw: float) -> Tuple[float, str]:
    return cost.least_time(*scan_step_cost(cfg, tokens, seq_len, passes),
                           peak_flops, peak_bw)
