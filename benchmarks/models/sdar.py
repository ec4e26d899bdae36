"""Family adapter: SDAR-30B-A3B-Chat's published ``config.json`` (``sdar_moe``)
through ``ray_tpu/models/llama.py``: ``head_dim`` apart from ``hidden_size /
num_attention_heads``, per-head QK-norm, ``models/moe.py``'s expert layer
holding the configuration's share of the experts, and block-diffusion
training (``models/block_diffusion.py``).

**The parameters come from the configuration's ``init_seed``, where it states
one, and not from ``--seed``**: a job adapts one checkpoint and its data
vary.  With a freshly drawn router a row's experts follow its token ids, so
across freshly drawn models the held experts' share of the rows, and with it
the step's time, wanders by more than the benchmark's bound allows (the
configuration file has the readings).  ``--seed`` still draws the documents,
their order and, through the rows, the noise.  A configuration without
``init_seed`` draws its parameters from the key the harness hands over, as
every other family does.

**The learning rate warms up** over the configuration's ``lr_warmup_steps``
where it states them: ``init_seed`` alone did not quieten the cell, because
Adam at the full rate from the first step collapses a freshly drawn router
inside the benchmark's window, onto experts the data choose (the
configuration file's ``assumed`` has the readings).

The traffic draws ids from ``[0, vocab_size - 1)``: the last id of the slice
is ``[MASK]`` and stays out of the data.
"""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.lib import cost_sdar
from benchmarks.lib.family import AttentionCall, Family
from benchmarks.reference import sdar as reference


def _learning_rate(warmup_steps: int):
    """3e-4 as the other families have it, reached linearly from zero over
    the configuration's ``lr_warmup_steps`` (0: from the first step)."""
    if not warmup_steps:
        return 3e-4
    import optax

    return optax.linear_schedule(0.0, 3e-4, warmup_steps)


def attention_calls(c: Dict):
    """The kinds of both covers of the mask, told apart by their lengths:
    ``whole``, the one call over a row's 2S x 2S positions that
    ``ops/attention.py:block_diffusion_attention`` makes today, and the two
    calls that would cover the same mask, ``noised`` (the noised copy's S
    queries over the 2S keys) and ``clean`` (the clean copy's S queries over
    its own S keys), whose areas sum to the whole mask's.  A step makes the
    one or the two; a kind that claims no call costs nothing."""
    heads = (c["num_attention_heads"], c["num_key_value_heads"],
             c["head_dim"], c["head_dim"])
    Bk = c["block_length"]
    return (
        AttentionCall("whole", *heads, q_len=2, kv_len=2,
                      pairs=lambda S: cost_sdar.mask_area(S, Bk)),
        AttentionCall("noised", *heads, q_len=1, kv_len=2,
                      pairs=lambda S: cost_sdar.noised_area(S, Bk)),
        AttentionCall("clean", *heads, q_len=1, kv_len=1,
                      pairs=lambda S: cost_sdar.clean_area(S, Bk)))


def build(config_file: Dict, seq_len: int) -> Family:
    import jax

    from ray_tpu.models import llama

    c = config_file
    if not hasattr(llama.LlamaConfig, "block_length"):
        raise SystemExit("this checkout's models/llama.py has no "
                         "block_length: family sdar cannot run here")
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("rope_scaling", None), ("use_sliding_window", False),
                      ("tie_word_embeddings", False), ("mlp_only_layers", []),
                      ("decoder_sparse_step", 1)):
        if c.get(key, want) != want:
            raise SystemExit(f"models/llama.py has no {key}={c[key]!r}")
    first, stop = c["experts_held"]
    if stop - first != c["num_experts"]:
        raise SystemExit(f"experts_held {c['experts_held']} is not the "
                         f"{c['num_experts']} experts num_experts counts")
    if c["mask_token_id"] != c["vocab_size"] - 1:
        raise SystemExit("[MASK] has to be the slice's last id: the traffic "
                         "draws from the ids below it")
    model = llama.LlamaConfig(
        vocab_size=c["vocab_size"], n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"],
        d_model=c["hidden_size"], head_dim=c["head_dim"],
        d_ff=c["moe_intermediate_size"], seq_len=seq_len,
        rope_theta=float(c["rope_theta"]), rms_eps=float(c["rms_norm_eps"]),
        n_experts=c["num_experts_published"],
        experts_per_token=c["num_experts_per_tok"],
        experts_held=range(first, stop), norm_topk_prob=c["norm_topk_prob"],
        qk_norm="head", router_aux_loss_coef=c["router_aux_loss_coef"],
        block_length=c["block_length"], mask_token_id=c["mask_token_id"],
        noise_seed=c["noise_seed"], **c.get("options", {}))

    def init_fn(key):
        if "init_seed" in c:
            key = jax.random.key(c["init_seed"])
        return llama.init_params(model, key)

    return Family(
        init_fn=init_fn,
        logical_axes=llama.logical_axes(model),
        make_optimizer=lambda: llama.make_optimizer(
            learning_rate=_learning_rate(c.get("lr_warmup_steps", 0))),
        make_train_step=functools.partial(llama.make_train_step, model),
        loss_fn=lambda p, t, y: llama.loss_fn(p, t, y, model),
        reference_loss=lambda p, t, y, q_block: reference.loss(
            p, t, y, c, q_block=q_block),
        flops_per_token=cost_sdar.model_flops_per_token(c, seq_len),
        attention_calls=attention_calls(c),
        vocab_size=c["vocab_size"] - 1, eod_id=c["eos_token_id"])
