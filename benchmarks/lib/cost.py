"""Operations and bytes an algorithm needs, from shapes alone.

Model FLOPs per token (what ``step.mfu`` divides by the peak):

    6 x parameters that sit in a matmul (every block matrix, the LM head once)
    + causal attention at half the square: 6 x layers x S x (heads x head_dim)

No recomputation is counted (remat re-runs forward matmuls and, on the Llama
path, the attention forward; those are the program's choice, not the model's),
the embedding gather is not a matmul, learned positions and norms are not
matmuls, and the LM head is counted over the published vocabulary, not over
rows the program pads.  Forward is 2 FLOPs per parameter per token, backward
4; attention forward is two S x S x head_dim matmuls per head (QK^T and PV),
4 x S x D FLOPs per token per layer at the full square, 12 x S x D with the
backward, 6 x S x D causal.

Attention kernel cost (what ``kernels.splash_roofline`` divides kernel time
into) is that of the flash algorithm the kernel implements: the forward call
does the two matmuls, the backward call five (it recomputes QK^T, then dV, dP,
dQ, dK), both over the causal half.  Bytes are one read of each input and one
write of each output in the kernel's dtype.
"""

from __future__ import annotations

from typing import Dict, Tuple


# ------------------------------------------------------------------ models
def llama_matmul_params(cfg: Dict) -> int:
    """Published-key config (hidden_size, ...) -> parameters in a matmul."""
    D, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    H, KV, F = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["intermediate_size"])
    hd = cfg.get("head_dim") or D // H
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return L * per_layer + V * D  # untied head; the embedding is a gather


def gpt2_matmul_params(cfg: Dict) -> int:
    D, L, V = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    per_layer = 3 * D * D + D * D + 8 * D * D  # qkv, out, mlp in + out
    return L * per_layer + V * D  # tied head used once as a matmul


def model_flops_per_token(matmul_params: int, n_layer: int, attn_width: int,
                          seq_len: int) -> float:
    """``attn_width`` is heads x head_dim."""
    return 6.0 * matmul_params + 6.0 * n_layer * seq_len * attn_width


# ----------------------------------------------------------------- kernels
def attention_call_cost(kind: str, batch: int, heads: int, seq: int,
                        head_dim: int, itemsize: int = 2,
                        causal: bool = True) -> Tuple[float, float]:
    """(FLOPs, bytes) of one flash-attention call over (batch, heads, seq,
    head_dim); ``kind`` is ``fwd`` or ``bwd``."""
    square = batch * heads * seq * seq * head_dim * (0.5 if causal else 1.0)
    tensor = batch * heads * seq * head_dim * itemsize
    if kind == "fwd":
        return 2 * 2.0 * square, 4.0 * tensor       # q k v -> o
    if kind == "bwd":
        return 5 * 2.0 * square, 8.0 * tensor       # q k v o do -> dq dk dv
    raise ValueError(f"attention call kind {kind!r} (use fwd|bwd)")


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> Tuple[float, str]:
    """The least seconds the chip could take, and which peak bounds it."""
    t_compute, t_memory = flops / peak_flops, nbytes / peak_bw
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
