"""Family adapter: XingChen-AGI's Xing4.0-29B-A4B published ``config.json``
(``xing4_0``: the ``deepseek_v3`` family's keys and the five of its
hyper-connections) through ``ray_tpu/models/hybrid.py``.  A layer is latent
attention and then a feed-forward part, two of ``hybrid.py``'s single-mixer
layers, ``LD`` below ``first_k_dense_replace`` and ``LE`` from there on, as
``models/joyai_llm_flash.py`` spells them; ``num_nextn_predict_layers`` is
the depth of the prediction module; ``hc_mult`` the residual's streams
(``ray_tpu/models/streams.py``), each sub-layer under maps of its own;
``rope_scaling`` (YaRN) the rotary table and the softmax's scale.  The
configuration's ``n_routed_experts`` counts the experts held here.

The parameters come from the configuration's ``init_seed``, ``--seed`` draws
the documents and their order, and the learning rate is ``sdar.py``'s, as the
other held-share families have it.

``Family.attention_calls`` states one kind: causal, every head a key head,
the q.k head ``qk_nope_head_dim + qk_rope_head_dim`` wide and the v head
``v_head_dim``.
"""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.lib import cost, cost_xing
from benchmarks.lib.family import AttentionCall, Family
from benchmarks.models.joyai_llm_flash import pattern
from benchmarks.models.sdar import _learning_rate
from benchmarks.reference import xing4_0 as reference


def model_config(c: Dict, seq_len: int):
    """The published keys as ``hybrid.HybridConfig``."""
    import dataclasses

    from ray_tpu.models import hybrid

    fields = {f.name for f in dataclasses.fields(hybrid.HybridConfig)}
    if "streams" not in fields or "mla_rope_yarn" not in fields:
        raise SystemExit("this checkout's ray_tpu/models/hybrid.py has no "
                         "residual of several streams (HybridConfig.streams) "
                         "or no YaRN under latent attention "
                         "(mla_rope_yarn): family xing4_0 cannot run here")
    from ray_tpu.models.layers import Yarn

    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("moe_layer_freq", 1), ("n_group", 1),
                      ("topk_group", 1), ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"),
                      ("tie_word_embeddings", False),
                      ("n_shared_experts", 1)):
        if c[key] != want:
            raise SystemExit(f"models/hybrid.py has no {key}={c[key]!r}")
    scaling = c["rope_scaling"]
    if scaling["type"] != "yarn":
        raise SystemExit(f"models/mla.py has no rope_scaling {scaling!r}")
    first, stop = c["experts_held"]
    if stop - first != c["n_routed_experts"]:
        raise SystemExit(f"experts_held {c['experts_held']} is not the "
                         f"{c['n_routed_experts']} experts n_routed_experts "
                         "counts")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise SystemExit("the key heads are not the query heads")
    if c["num_nextn_predict_layers"] not in (0, 1):
        raise SystemExit("models/hybrid.py has one prediction module or "
                         "none")
    if c["hc_mult"] < 2:
        raise SystemExit("hc_mult 1 is the plain residual: family "
                         "joyai_llm_flash's")
    all_dim = reference.mscale(scaling["factor"], scaling["mscale_all_dim"])
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return hybrid, hybrid.HybridConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        pattern=pattern(c), seq_len=seq_len,
        rms_eps=float(c["rms_norm_eps"]),
        mla_heads=c["num_attention_heads"], mla_q_latent=c["q_lora_rank"],
        mla_kv_latent=c["kv_lora_rank"], mla_nope_dim=c["qk_nope_head_dim"],
        mla_rope_dim=c["qk_rope_head_dim"], mla_v_dim=c["v_head_dim"],
        mla_rope_theta=float(c["rope_theta"]), mla_rope_interleave=True,
        mla_rope_yarn=Yarn(
            factor=float(scaling["factor"]),
            original=scaling["original_max_position_embeddings"],
            beta_fast=float(scaling["beta_fast"]),
            beta_slow=float(scaling["beta_slow"]),
            attention_factor=reference.mscale(
                scaling["factor"], scaling["mscale"]) / all_dim),
        mla_sm_scale=qk ** -0.5 * all_dim * all_dim,
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
        streams=c["hc_mult"], hc_sinkhorn_iters=c["hc_sinkhorn_iters"],
        hc_eps=float(c["hc_eps"]),
        hc_clamp=(float(c["mhc_h_res_clamp_min"]),
                  float(c["mhc_h_res_clamp_max"])),
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
        flops_per_token=cost_xing.model_flops_per_token(c, seq_len),
        attention_calls=(AttentionCall(
            "causal", c["num_attention_heads"], c["num_key_value_heads"],
            c["qk_nope_head_dim"] + c["qk_rope_head_dim"], c["v_head_dim"],
            pairs=cost.causal_pairs),),
        vocab_size=c["vocab_size"], eod_id=c["eos_token_id"])
