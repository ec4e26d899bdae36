"""Family adapter: Upstage Solar-Open2's published ``config.json``
(``solar_open2``) through ``ray_tpu/models/hybrid.py``.  A Solar layer is a
token mixer and then experts, two of ``hybrid.py``'s single-mixer layers:
layer i is ``*E`` where ``gqa_layers`` names i (softmax attention without
rotary embedding, with an output gate) and ``KE`` elsewhere (gated delta-rule
linear attention with a per-channel decay), every layer with experts
(``first_k_dense_replace`` 0).  The configuration's head counts are the share
held here (``heads_held``), its ``n_routed_experts`` the experts held.

As for ``nemotron_h``: the parameters come from the configuration's
``init_seed`` where it states one, and not from ``--seed``; ``--seed`` draws
the documents and their order; the learning rate is ``sdar.py``'s, the other
families' 3e-4 reached linearly from zero over the configuration's
``lr_warmup_steps``.
"""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.lib import cost_solar
from benchmarks.lib.family import Family, causal
from benchmarks.models.sdar import _learning_rate
from benchmarks.reference import solar_open2 as reference


def pattern(c: Dict) -> str:
    """``hybrid.py``'s letters for the configuration's layers."""
    return "".join(("*" if i in c["gqa_layers"] else "K") + "E"
                   for i in range(c["num_hidden_layers"]))


def model_config(c: Dict, seq_len: int):
    """The published keys as ``hybrid.HybridConfig``."""
    from ray_tpu.models import hybrid

    if "K" not in hybrid.KINDS:
        raise SystemExit("this checkout's ray_tpu/models/hybrid.py has no "
                         "KDA layer kind (K): family solar_open2 cannot run "
                         "here")
    linear = c["linear_attn_config"]
    for key, want in (("use_rope", False), ("use_gqa_gate", True),
                      ("kda_use_full_proj", False),
                      ("kda_allow_neg_eigval", True),
                      ("first_k_dense_replace", 0),
                      ("tie_word_embeddings", False),
                      ("n_shared_experts", 1)):
        if c[key] != want:
            raise SystemExit(f"models/hybrid.py has no {key}={c[key]!r}")
    first, stop = c["experts_held"]
    if stop - first != c["n_routed_experts"]:
        raise SystemExit(f"experts_held {c['experts_held']} is not the "
                         f"{c['n_routed_experts']} experts n_routed_experts "
                         "counts")
    first, stop = c["heads_held"]
    share = c["num_attention_heads_published"] // (stop - first)
    if not (stop - first == linear["num_heads"] == c["num_attention_heads"]
            and c["num_key_value_heads"] * share
            == c["num_key_value_heads_published"]):
        raise SystemExit(f"heads_held {c['heads_held']} is not the share "
                         "the head counts state")
    return hybrid, hybrid.HybridConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        pattern=pattern(c), seq_len=seq_len,
        rms_eps=float(c["rms_norm_eps"]),
        n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"],
        head_dim=c["head_dim"], rope_theta=None, attn_gate=True,
        n_head_total=c["num_attention_heads_published"],
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        kda_conv=linear["short_conv_kernel_size"], kda_chunk=c["kda_chunk"],
        time_step_min=c["time_step_min"], time_step_max=c["time_step_max"],
        time_step_floor=c["time_step_floor"],
        n_experts=c["n_routed_experts_published"],
        experts_per_token=c["num_experts_per_tok"],
        d_ff=c["moe_intermediate_size"],
        shared_width=c["n_shared_experts"] * c["moe_intermediate_size"],
        expert_activation="silu", gated_experts=True,
        norm_topk_prob=c["norm_topk_prob"], router_scoring="sigmoid",
        routed_scaling=float(c["routed_scaling_factor"]),
        experts_held=range(*c["experts_held"]), router_bias_std=0.0,
        **c.get("options", {}))


def build(config_file: Dict, seq_len: int) -> Family:
    import jax

    c = config_file
    hybrid, model = model_config(c, seq_len)

    def init_fn(key):
        if "init_seed" in c:
            key = jax.random.key(c["init_seed"])
        return hybrid.init_params(model, key)

    return Family(
        init_fn=init_fn,
        logical_axes=hybrid.logical_axes(model),
        make_optimizer=lambda: hybrid.make_optimizer(
            learning_rate=_learning_rate(c.get("lr_warmup_steps", 0))),
        make_train_step=functools.partial(hybrid.make_train_step, model),
        loss_fn=lambda p, t, y: hybrid.loss_fn(p, t, y, model),
        reference_loss=lambda p, t, y, q_block: reference.loss(
            p, t, y, c, q_block=q_block),
        flops_per_token=cost_solar.model_flops_per_token(c, seq_len),
        attention_calls=(causal(c["num_attention_heads"],
                                c["num_key_value_heads"], c["head_dim"]),),
        vocab_size=c["vocab_size"], eod_id=c["eos_token_id"])
