"""Operations and bytes of Olmo-Hybrid's training step on one chip, from
shapes alone (``lib/cost.py``'s rules: no recomputation counted in the
model's FLOPs; norms, the embedding gather, the convolutions and the gates'
elementwise parts are not matmuls).

Model FLOPs per trained token: 6 x the matrix parameters a position meets (a
``linear_attention`` layer's q, k, v, output gate and o, its two columns a
head for the decay and beta; a ``full_attention`` layer's q, k, v and o;
every layer's three-matrix MLP; the head once) plus causal attention at half
the square and the delta-rule scan.

**The scan** (``ray_tpu/ops/gdn.py``; H heads with keys of dk and values of
dv, chunks of C), a position a head, forward, in multiply-adds:

    A = K K^T and B = Q K^T                          C x dk / 2 each (causal)
    T [V | Kbar]                                     C x (dv + dk) / 2
                                                     (triangular)
    B U                                              C x dv / 2 (causal)
    (T Kbar) S_0,  Qbar S_0,  Kend^T U               dk x dv each

about 154 k FLOPs at C = 64, dk = 96, dv = 192; the decays (one (C x C)
array of ``exp`` a chunk) and the triangular system's solution are not
counted (an implementation may get them as it likes).  2 FLOPs a
multiply-add forward, 4 more backward.  What the scan cannot avoid moving,
each way: q and k (H x dk a position each, the compute dtype), v and o (H x
dv), g and beta (H, float32), and the chunk states out and in (H x dk x dv
float32 a chunk, twice).  The backward reads the same again and writes
their cotangents.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmarks.lib import cost

KINDS = {"linear_attention": "G", "full_attention": "*"}


def layers(cfg: Dict) -> Tuple[int, int]:
    """(linear layers, full layers) of the configuration's depth: the
    published ``layer_types``, read by index."""
    run = cfg["layer_types"][:cfg["num_hidden_layers"]]
    linear = sum(kind == "linear_attention" for kind in run)
    return linear, len(run) - linear


def _linear_dims(cfg: Dict) -> Tuple[int, int, int]:
    return (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def layer_matmul_params(cfg: Dict) -> Dict[str, int]:
    """Matrix parameters one position meets in a linear mixer, a full mixer
    and a layer's MLP."""
    D = cfg["hidden_size"]
    H, dk, dv = _linear_dims(cfg)
    hd = D // cfg["num_attention_heads"]
    return {
        "linear": D * H * (2 * dk + 3 * dv + 2),
        "full": 2 * D * hd * (cfg["num_attention_heads"]
                              + cfg["num_key_value_heads"]),
        "mlp": 3 * D * cfg["intermediate_size"],
    }


def params_held(cfg: Dict) -> int:
    """Every parameter that exists on this chip: the matrices, the taps, the
    decay's two vectors a head, the norms (one a sub-layer, a linear layer's
    over a head's values, a full layer's two over all of q and of k), the
    embedding and the head over the slice of the vocabulary."""
    D = cfg["hidden_size"]
    H, dk, dv = _linear_dims(cfg)
    hd = D // cfg["num_attention_heads"]
    linear, full = layers(cfg)
    met = layer_matmul_params(cfg)
    return (linear * (met["linear"] + cfg["linear_conv_kernel_dim"] * H
                      * (2 * dk + dv) + 2 * H + dv + D)
            + full * (met["full"] + D + hd * (cfg["num_attention_heads"]
                                              + cfg["num_key_value_heads"]))
            + (linear + full) * (met["mlp"] + D)
            + 2 * cfg["vocab_size"] * D + D)


def scan_flops_per_position(cfg: Dict, seq_len: int) -> float:
    """Forward FLOPs of one linear layer's scan a position."""
    H, dk, dv = _linear_dims(cfg)
    C = min(cfg["gdn_chunk"], seq_len)
    return 2.0 * H * (C * (1.5 * dk + dv) + 3 * dk * dv)


def model_flops_per_token(cfg: Dict, seq_len: int) -> float:
    linear, full = layers(cfg)
    met = layer_matmul_params(cfg)
    matmuls = linear * met["linear"] + full * met["full"] \
        + (linear + full) * met["mlp"] \
        + cfg["vocab_size"] * cfg["hidden_size"]
    attention = 6.0 * full * seq_len * cfg["hidden_size"]
    return 6.0 * matmuls + attention \
        + 3.0 * linear * scan_flops_per_position(cfg, seq_len)


def scan_step_cost(cfg: Dict, tokens: int, seq_len: int, passes: float,
                   itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of every linear layer's scan for ``tokens`` positions
    of a step.  ``passes`` counts forward passes: 1 forward, 2 more for the
    backward, 1 more where the layer's checkpoint runs the forward again."""
    H, dk, dv = _linear_dims(cfg)
    C = min(cfg["gdn_chunk"], seq_len)
    n = layers(cfg)[0]
    flops = scan_flops_per_position(cfg, seq_len) * tokens
    a_position = H * (2 * (dk + dv) * itemsize + 2 * 4)
    states = 2 * H * dk * dv * 4 / C
    return n * passes * flops, n * passes * tokens * (a_position + states)


def scan_least_time(cfg: Dict, tokens: int, seq_len: int, passes: float,
                    peak_flops: float, peak_bw: float) -> Tuple[float, str]:
    return cost.least_time(*scan_step_cost(cfg, tokens, seq_len, passes),
                           peak_flops, peak_bw)
