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
into) is that of the flash algorithm the kernel implements, over the pairs the
call's own mask allows (``lib/family.py:AttentionCall``: the family states
each kind of call its step makes; a causal call's pairs are half the square,
``causal_pairs``: the diagonal's half, S / 2 pairs, is left out).  The
forward call is QK^T over the q.k head and PV over the v head, ``2 x (qk +
v)`` FLOPs a pair; the fused backward recomputes QK^T and makes dV, dP, dQ
and dK, three products over the q.k head and two over the v head, ``2 x (3 qk
+ 2 v)`` a pair (4 and 10 times the head dimension where the two are one).
Bytes are one read of each input and one write of each output in the kernel's
dtype: q and o (do, dq) at the query heads and length, k and v (dk, dv) at
the key/value heads and length the kernel is handed, q and k at the q.k head
dimension and v and o at v's: the model's own, whatever an implementation
pads.
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
def causal_pairs(seq_len: int) -> float:
    """Pairs a head of a causal call over ``seq_len`` positions is charged:
    half the square."""
    return seq_len * seq_len / 2


def attention_call_cost(kind: str, batch: int, call, seq_len: int,
                        itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one flash-attention call of the kind ``call``
    (``lib/family.py:AttentionCall``) over ``batch`` rows of a cell whose
    sequence length is ``seq_len``; ``kind`` is ``fwd`` or ``bwd``."""
    qk, v = call.qk_dim, call.v_dim
    pairs = batch * call.q_heads * call.pairs(seq_len)
    q_like = batch * call.q_heads * call.q_len * seq_len * itemsize
    kv_like = batch * call.kv_heads * call.kv_len * seq_len * itemsize
    once = (qk + v) * (q_like + kv_like)            # q o; k v
    if kind == "fwd":    # q k v -> o
        return 2.0 * (qk + v) * pairs, float(once)
    if kind == "bwd":    # q k v o do -> dq dk dv
        return 2.0 * (3 * qk + 2 * v) * pairs, 2.0 * once
    raise ValueError(f"attention call kind {kind!r} (use fwd|bwd)")


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> Tuple[float, str]:
    """The least seconds the chip could take, and which peak bounds it."""
    t_compute, t_memory = flops / peak_flops, nbytes / peak_bw
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
