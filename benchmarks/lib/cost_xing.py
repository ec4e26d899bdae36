"""Operations and bytes of Xing4.0-29B-A4B's training step on a chip that
holds a share of the routed experts and of the vocabulary, from shapes alone
(``lib/cost.py``'s rules: no recomputation counted in the model's FLOPs;
norms, the embedding gathers, the rotary passes, the gates' elementwise
parts, the routing's sort and gathers, and the stream maps' sigmoids, turns,
read and write are not matmuls).

Model FLOPs per trained token: ``lib/cost_joyai.py``'s count of the
``deepseek_v3`` family's layers (latent attention, the leading dense MLPs,
the expert layers with the held experts in expectation, the head, the
prediction module's block and second pass through the head, causal attention
at half the square), and what the residual of ``hc_mult`` = n streams adds:
a sub-layer's maps multiply the n x hidden lanes of a position by ``phi``,
n x hidden x (n^2 + 2n) entries, on every sub-layer (two a published layer,
the module's two among them); and the module's ``w_eh`` meets the next
token's embedding once and the hidden state a stream, (1 + n) x hidden^2 in
the place of 2 x hidden^2.

**The least bytes of the streams' read and write** (what
``step.mhc_mix_roofline`` divides by the chip's HBM bandwidth and holds
against the hyper-connections' time), in arrays of
(tokens, hidden) in the compute dtype, n = ``hc_mult``.  A sub-layer's
forward: the read takes the n streams and writes ``u`` (n + 1); the write
takes them again, and ``y``, and writes the n new ones (2n + 1): the branch
runs between the two and ``X`` is 28 KB a position, so nothing holds it on
the chip meanwhile: 3n + 2, 14 at n = 4.  The backward: the write's takes
the n cotangents of ``X'``, ``X`` (for the maps' cotangent) and ``y``, and
writes the cotangent of ``y`` and the stream map's share of ``X``'s (3n + 2);
the read's, after the branch's own backward, takes the cotangent of ``u``,
``X`` and that share and writes ``X``'s cotangent (3n + 1): 6n + 3, 27.  A
second forward that a layer's checkpoint runs in the backward needs ``u``
again and not ``X'``: the read alone, n + 1, counted where the trace shows
it.  46 arrays a sub-layer at n = 4 with it.  The maps themselves (n^2 + 2n
float32 a position) are under a hundredth of that and are left out: the
same work whatever implements it.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lib import cost_joyai


def sublayers(cfg: Dict) -> int:
    """Sub-layers under maps: two a published layer, the module's too."""
    return 2 * (cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"])


def maps_matmul_params(cfg: Dict) -> int:
    """``phi``'s entries, which one position meets in one sub-layer."""
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * (n * n + 2 * n)


def params_held(cfg: Dict) -> int:
    """Every parameter that exists on this chip: ``cost_joyai``'s and each
    sub-layer's ``phi``, three ``alpha`` and n^2 + 2n ``base``."""
    n = cfg["hc_mult"]
    return cost_joyai.params_held(cfg) + sublayers(cfg) * (
        maps_matmul_params(cfg) + 3 + n * n + 2 * n)


def model_flops_per_token(cfg: Dict, seq_len: int) -> float:
    D, n = cfg["hidden_size"], cfg["hc_mult"]
    return cost_joyai.model_flops_per_token(cfg, seq_len) + 6.0 * (
        sublayers(cfg) * maps_matmul_params(cfg)
        + cfg["num_nextn_predict_layers"] * (n - 1) * D * D)


def mix_bytes(cfg: Dict, tokens: int, recomputed: bool,
              itemsize: int = 2) -> float:
    """The least bytes a step's reads and writes of the streams move over
    ``tokens`` positions, all sub-layers: forward, backward and, with
    ``recomputed``, the read once more."""
    n = cfg["hc_mult"]
    arrays = (3 * n + 2) + (6 * n + 3) + (n + 1 if recomputed else 0)
    return float(sublayers(cfg) * arrays * tokens * cfg["hidden_size"]
                 * itemsize)
