"""Operations of a looped decoder, from shapes alone (``lib/cost.py``'s
rules: nothing recomputed is counted, norms and the embedding gather are not
matmuls, the head is counted over the published vocabulary).

A step runs the stack of ``num_hidden_layers`` layers ``total_ut_steps``
times over with one set of weights, and every pass ends in the head and the
exit gate.  So a token meets each block matrix, the causal attention, the
head and the gate's ``hidden_size`` weights ``T`` times, and
``lib/cost.py:model_flops_per_token``, which counts each once, would read a
quarter of the model's work:

    6 x T x (layers' matmul parameters + vocab x hidden + hidden)
    + 6 x T x layers x S x (heads x head_dim)
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lib import cost


def matmul_params_a_pass(cfg: Dict) -> int:
    """Published-key config -> the matmul parameters one pass of one token
    meets: the layers' seven matrices, the head, the gate's vector."""
    # llama_matmul_params counts the layers and the untied head once
    return cost.llama_matmul_params(cfg) + cfg["hidden_size"]


def model_flops_per_token(cfg: Dict, seq_len: int) -> float:
    T = cfg["total_ut_steps"]
    return T * cost.model_flops_per_token(
        matmul_params_a_pass(cfg), cfg["num_hidden_layers"],
        cfg["num_attention_heads"] * cfg["head_dim"], seq_len)
