"""Family adapter: a published GPT-2 ``config.json`` (GPT-2 XL) through
``ray_tpu/models/gpt2.py``."""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.lib import cost
from benchmarks.lib.family import Family, causal
from benchmarks.reference import gpt2 as reference


def build(config_file: Dict, seq_len: int) -> Family:
    from ray_tpu.models import gpt2

    c = config_file
    if seq_len > c["n_positions"]:
        raise SystemExit(
            f"S={seq_len} is beyond the model's {c['n_positions']} positions")
    if float(c["layer_norm_epsilon"]) != 1e-5:
        raise SystemExit("models/gpt2.py fixes the LayerNorm eps at 1e-5")
    # The embedding holds padded_vocab_size rows, as the program pads it for
    # the MXU; ids are drawn from the published vocab_size only.
    model = gpt2.GPTConfig(
        vocab_size=c["padded_vocab_size"], n_layer=c["n_layer"],
        n_head=c["n_head"], d_model=c["n_embd"], seq_len=seq_len,
        **c.get("options", {}))
    return Family(
        init_fn=functools.partial(gpt2.init_params, model),
        logical_axes=gpt2.logical_axes(model),
        make_optimizer=lambda: gpt2.make_optimizer(learning_rate=3e-4),
        make_train_step=functools.partial(gpt2.make_train_step, model),
        loss_fn=lambda p, t, y: gpt2.loss_fn(p, t, y, model),
        reference_loss=lambda p, t, y, q_block: reference.loss(
            p, t, y, c, q_block=q_block),
        flops_per_token=cost.model_flops_per_token(
            cost.gpt2_matmul_params(c), c["n_layer"], c["n_embd"], seq_len),
        attention_calls=(causal(c["n_head"], c["n_head"],
                                c["n_embd"] // c["n_head"]),),
        vocab_size=c["vocab_size"], eod_id=c["eos_token_id"])
