"""Family adapter: JD's JoyAI-LLM-Flash published ``config.json``
(``joyai_llm_flash``, the ``deepseek_v3`` family's keys) through
``ray_tpu/models/hybrid.py``.  A layer is latent attention and then a
feed-forward part, two of ``hybrid.py``'s single-mixer layers: layer i is
``LD`` (a dense SwiGLU MLP) below ``first_k_dense_replace`` and ``LE``
(experts) from there on; ``num_nextn_predict_layers`` is the depth of the
prediction module after the last layer, one more ``LE`` block.  The
configuration's ``n_routed_experts`` counts the experts held here.

As for ``nemotron_h``: the parameters come from the configuration's
``init_seed`` where it states one, and not from ``--seed``; ``--seed`` draws
the documents and their order; the learning rate is ``sdar.py``'s, the other
families' 3e-4 reached linearly from zero over the configuration's
``lr_warmup_steps``.

``Family.attention_calls`` states one kind: causal, every head a key head,
the q.k head ``qk_head_dim`` wide and the v head ``v_head_dim`` (192 and 128:
``lib/cost.py`` counts the two dimensions apart).
"""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.lib import cost, cost_joyai
from benchmarks.lib.family import AttentionCall, Family
from benchmarks.models.sdar import _learning_rate
from benchmarks.reference import joyai_llm_flash as reference


def pattern(c: Dict) -> str:
    """``hybrid.py``'s letters for the configuration's layers."""
    return "".join("L" + ("D" if i < c["first_k_dense_replace"] else "E")
                   for i in range(c["num_hidden_layers"]))


def model_config(c: Dict, seq_len: int):
    """The published keys as ``hybrid.HybridConfig``."""
    from ray_tpu.models import hybrid

    missing = [kind for kind in "LD" if kind not in hybrid.KINDS]
    if missing:
        raise SystemExit("this checkout's ray_tpu/models/hybrid.py has no "
                         f"layer kind {' or '.join(missing)} (latent "
                         "attention, a leading dense layer): family "
                         "joyai_llm_flash cannot run here")
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("moe_layer_freq", 1), ("n_group", 1),
                      ("topk_group", 1), ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("rope_scaling", None),
                      ("tie_word_embeddings", False),
                      ("n_shared_experts", 1)):
        if c[key] != want:
            raise SystemExit(f"models/hybrid.py has no {key}={c[key]!r}")
    first, stop = c["experts_held"]
    if stop - first != c["n_routed_experts"]:
        raise SystemExit(f"experts_held {c['experts_held']} is not the "
                         f"{c['n_routed_experts']} experts n_routed_experts "
                         "counts")
    if c["qk_head_dim"] != c["qk_nope_head_dim"] + c["qk_rope_head_dim"] \
            or c["num_key_value_heads"] != c["num_attention_heads"]:
        raise SystemExit("qk_head_dim is not its two parts, or the key "
                         "heads are not the query heads")
    if c["num_nextn_predict_layers"] not in (0, 1):
        raise SystemExit("models/hybrid.py has one prediction module or "
                         "none")
    return hybrid, hybrid.HybridConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        pattern=pattern(c), seq_len=seq_len,
        rms_eps=float(c["rms_norm_eps"]),
        mla_heads=c["num_attention_heads"], mla_q_latent=c["q_lora_rank"],
        mla_kv_latent=c["kv_lora_rank"], mla_nope_dim=c["qk_nope_head_dim"],
        mla_rope_dim=c["qk_rope_head_dim"], mla_v_dim=c["v_head_dim"],
        mla_rope_theta=float(c["rope_theta"]),
        mla_rope_interleave=c["rope_interleave"],
        dense_width=c["intermediate_size"],
        n_experts=c["n_routed_experts_published"],
        experts_per_token=c["num_experts_per_tok"],
        d_ff=c["moe_intermediate_size"],
        shared_width=c["n_shared_experts"] * c["moe_intermediate_size"],
        expert_activation="silu", gated_experts=True,
        norm_topk_prob=c["norm_topk_prob"], router_scoring="sigmoid",
        routed_scaling=float(c["routed_scaling_factor"]),
        experts_held=range(first, stop),
        router_bias_seed=c.get("router_bias_seed", 0),
        router_bias_std=c.get("router_bias_std", 0.0),
        mtp_depth=c["num_nextn_predict_layers"],
        mtp_weight=float(c["mtp_loss_weight"]),
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
        flops_per_token=cost_joyai.model_flops_per_token(c, seq_len),
        attention_calls=(AttentionCall(
            "causal", c["num_attention_heads"], c["num_key_value_heads"],
            c["qk_head_dim"], c["v_head_dim"], pairs=cost.causal_pairs),),
        vocab_size=c["vocab_size"], eod_id=c["eos_token_id"])
