"""Family adapter: a published Llama-style ``config.json`` (Mistral-7B-v0.3)
through ``ray_tpu/models/llama.py``."""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.lib import cost
from benchmarks.lib.family import Family, causal
from benchmarks.reference import llama as reference


def build(config_file: Dict, seq_len: int) -> Family:
    from ray_tpu.models import llama

    c = config_file
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    if hd * c["num_attention_heads"] != c["hidden_size"]:
        raise SystemExit("models/llama.py derives head_dim from hidden_size")
    if c.get("sliding_window") is not None:
        raise SystemExit("models/llama.py has no sliding-window attention")
    model = llama.LlamaConfig(
        vocab_size=c["vocab_size"], n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"],
        d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        seq_len=seq_len, rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]), **c.get("options", {}))
    return Family(
        init_fn=functools.partial(llama.init_params, model),
        logical_axes=llama.logical_axes(model),
        make_optimizer=lambda: llama.make_optimizer(learning_rate=3e-4),
        make_train_step=functools.partial(llama.make_train_step, model),
        loss_fn=lambda p, t, y: llama.loss_fn(p, t, y, model),
        reference_loss=lambda p, t, y, q_block: reference.loss(
            p, t, y, c, q_block=q_block),
        flops_per_token=cost.model_flops_per_token(
            cost.llama_matmul_params(c), c["num_hidden_layers"],
            c["num_attention_heads"] * hd, seq_len),
        # the kernel is handed k and v at their own head count
        attention_calls=(causal(c["num_attention_heads"],
                                c["num_key_value_heads"], hd),),
        vocab_size=c["vocab_size"], eod_id=c["eos_token_id"])
