"""Family adapter: NVIDIA Nemotron-3-Nano's published ``config.json``
(``nemotron_h``) through ``ray_tpu/models/hybrid.py``: a stack of Mamba-2,
attention and expert layers in the order ``hybrid_override_pattern`` spells
out, the expert layers holding the configuration's share of the routed
experts.

As for ``sdar``: the parameters come from the configuration's ``init_seed``
where it states one, and not from ``--seed`` (a job adapts one checkpoint
and its data vary; with a freshly drawn router the held experts' share of
the rows, and with it the step's time, would follow the seed), and the
learning rate warms up over its ``lr_warmup_steps``.  ``--seed`` draws the
documents and their order.
"""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.lib import cost_nemotron
from benchmarks.lib.family import Family, causal
from benchmarks.models.sdar import _learning_rate
from benchmarks.reference import nemotron_h as reference


def model_config(c: Dict, seq_len: int):
    """The published keys as ``hybrid.HybridConfig``."""
    import importlib.util

    if importlib.util.find_spec("ray_tpu.models.hybrid") is None:
        raise SystemExit("this checkout has no ray_tpu/models/hybrid.py (a "
                         "decoder that is a list of layer kinds): family "
                         "nemotron_h cannot run here")
    from ray_tpu.models import hybrid

    for key, want in (("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"),
                      ("attention_bias", False), ("mlp_bias", False),
                      ("mamba_proj_bias", False), ("use_bias", False),
                      ("use_conv_bias", True), ("n_group", 1),
                      ("topk_group", 1), ("tie_word_embeddings", False),
                      ("n_shared_experts", 1)):
        if c.get(key, want) != want:
            raise SystemExit(f"models/hybrid.py has no {key}={c[key]!r}")
    first, stop = c["experts_held"]
    if stop - first != c["n_routed_experts"]:
        raise SystemExit(f"experts_held {c['experts_held']} is not the "
                         f"{c['n_routed_experts']} experts n_routed_experts "
                         "counts")
    if len(c["hybrid_override_pattern"]) != c["num_hidden_layers"]:
        raise SystemExit("hybrid_override_pattern does not spell out "
                         f"{c['num_hidden_layers']} layers")
    return hybrid, hybrid.HybridConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        pattern=c["hybrid_override_pattern"], seq_len=seq_len,
        rms_eps=float(c["norm_eps"]),
        n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"],
        head_dim=c["head_dim"], rope_theta=None,
        ssm_heads=c["mamba_num_heads"], ssm_head_dim=c["mamba_head_dim"],
        ssm_groups=c["n_groups"], ssm_state=c["ssm_state_size"],
        ssm_chunk=c["chunk_size"], ssm_conv=c["conv_kernel"],
        time_step_min=c["time_step_min"], time_step_max=c["time_step_max"],
        time_step_floor=c["time_step_floor"],
        gate_norm_eps=float(c["layer_norm_epsilon"]),
        n_experts=c["n_routed_experts_published"],
        experts_per_token=c["num_experts_per_tok"],
        d_ff=c["moe_intermediate_size"],
        shared_width=c["n_shared_experts"]
        * c["moe_shared_expert_intermediate_size"],
        norm_topk_prob=c["norm_topk_prob"], router_scoring="sigmoid",
        routed_scaling=float(c["routed_scaling_factor"]),
        experts_held=range(first, stop),
        router_bias_seed=c.get("router_bias_seed", 0),
        router_bias_std=c.get("router_bias_std", 0.0),
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
        flops_per_token=cost_nemotron.model_flops_per_token(c, seq_len),
        attention_calls=(causal(c["num_attention_heads"],
                                c["num_key_value_heads"], c["head_dim"]),),
        vocab_size=c["vocab_size"], eod_id=c["eos_token_id"])
