"""Family adapter: Ouro-2.6B's published ``config.json`` through
``ray_tpu/models/llama.py`` with ``models/looped.py``: the layers run
``total_ut_steps`` times over with one set of weights, a norm on both sides
of every sub-layer, the head and an exit gate after every pass.  The
learning rate is the Llama families' 3e-4, reached linearly from zero over the
configuration's ``lr_warmup_steps`` (``sdar.py``'s schedule; 0: from the
first step)."""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.lib import cost_ouro
from benchmarks.lib.family import Family, causal
from benchmarks.models.sdar import _learning_rate
from benchmarks.reference import ouro as reference


def model_config(c: Dict, seq_len: int):
    """(``ray_tpu.models.llama``, the ``LlamaConfig`` of the configuration
    file ``c`` at ``seq_len``)."""
    from ray_tpu.models import llama

    if not hasattr(llama.LlamaConfig, "ut_steps"):
        raise SystemExit("this checkout's models/llama.py runs its layers "
                         "once: family ouro cannot run here")
    for key, want in (("hidden_act", "silu"), ("rope_scaling", None),
                      ("sliding_window", None), ("use_sliding_window", False),
                      ("tie_word_embeddings", False)):
        if c.get(key, want) != want:
            raise SystemExit(f"models/llama.py has no {key}={c[key]!r}")
    if set(c["layer_types"]) != {"full_attention"} \
            or len(c["layer_types"]) != c["num_hidden_layers"]:
        raise SystemExit("models/llama.py runs full attention on every one "
                         f"of its layers, not {c['layer_types']}")
    return llama, llama.LlamaConfig(
        vocab_size=c["vocab_size"], n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"],
        d_model=c["hidden_size"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], seq_len=seq_len,
        rope_theta=float(c["rope_theta"]), rms_eps=float(c["rms_norm_eps"]),
        ut_steps=c["total_ut_steps"], sandwich_norm=True,
        exit_beta=c["exit_beta"], **c.get("options", {}))


def build(config_file: Dict, seq_len: int) -> Family:
    c = config_file
    llama, model = model_config(c, seq_len)
    return Family(
        init_fn=functools.partial(llama.init_params, model),
        logical_axes=llama.logical_axes(model),
        make_optimizer=lambda: llama.make_optimizer(
            learning_rate=_learning_rate(c.get("lr_warmup_steps", 0))),
        make_train_step=functools.partial(llama.make_train_step, model),
        loss_fn=lambda p, t, y: llama.loss_fn(p, t, y, model),
        reference_loss=lambda p, t, y, q_block: reference.loss(
            p, t, y, c, q_block=q_block),
        flops_per_token=cost_ouro.model_flops_per_token(c, seq_len),
        # the step makes the call ``T x L`` times; the kind is one
        attention_calls=(causal(c["num_attention_heads"],
                                c["num_key_value_heads"], c["head_dim"]),),
        vocab_size=c["vocab_size"], eod_id=c["eos_token_id"])
