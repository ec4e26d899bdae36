"""Family adapter: OLMoE-1B-7B's published ``config.json`` through
``ray_tpu/models/llama.py`` (QK-norm, and ``models/moe.py``'s dropless expert
layer in place of the SwiGLU MLP)."""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.lib import cost_moe
from benchmarks.lib.family import Family, causal
from benchmarks.reference import olmoe as reference


def build(config_file: Dict, seq_len: int) -> Family:
    from ray_tpu.models import llama

    c = config_file
    if not hasattr(llama.LlamaConfig, "n_experts"):
        raise SystemExit("this checkout's models/llama.py has no experts: "
                         "family olmoe cannot run here")
    hd = c["hidden_size"] // c["num_attention_heads"]
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("clip_qkv", None), ("rope_scaling", None),
                      ("tie_word_embeddings", False)):
        if c.get(key, want) != want:
            raise SystemExit(f"models/llama.py has no {key}={c[key]!r}")
    model = llama.LlamaConfig(
        vocab_size=c["vocab_size"], n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"],
        d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        seq_len=seq_len, rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]), n_experts=c["num_experts"],
        experts_per_token=c["num_experts_per_tok"],
        norm_topk_prob=c["norm_topk_prob"], qk_norm=True,
        router_aux_loss_coef=c["router_aux_loss_coef"],
        router_z_loss_coef=c["router_z_loss_coef"], **c.get("options", {}))
    return Family(
        init_fn=functools.partial(llama.init_params, model),
        logical_axes=llama.logical_axes(model),
        make_optimizer=lambda: llama.make_optimizer(learning_rate=3e-4),
        make_train_step=functools.partial(llama.make_train_step, model),
        loss_fn=lambda p, t, y: llama.loss_fn(p, t, y, model),
        reference_loss=lambda p, t, y, q_block: reference.loss(
            p, t, y, c, q_block=q_block),
        flops_per_token=cost_moe.model_flops_per_token(c, seq_len),
        attention_calls=(causal(c["num_attention_heads"],
                                c["num_key_value_heads"], hd),),
        vocab_size=c["vocab_size"], eod_id=c["eos_token_id"])
