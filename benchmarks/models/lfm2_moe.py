"""Family adapter: Liquid AI's LFM2 mixture-of-experts published
``config.json`` (``lfm2_moe``; LFM2-8B-A1B) through
``ray_tpu/models/hybrid.py``.  A layer is a token mixer and then a
feed-forward part, two of ``hybrid.py``'s single-mixer layers: layer i is
``C`` (a gated short convolution of ``conv_L_cache`` taps) where
``layer_types`` says ``conv`` and ``*`` (rotary GQA with an RMSNorm a head on
q and k) where it says ``full_attention``, then ``D`` (a dense SwiGLU MLP
``intermediate_size`` wide) on the first ``num_dense_layers`` layers and
``E`` (experts, no shared one) after them.  ``layer_types`` is the published
list, whole, read by index.

**Which layers a cut runs** (:func:`layers_run`): the leading dense layers
count once (``model-configs``, section 4), so a cut of ``num_hidden_layers``
n is layer 0 and then the n - 1 layers from ``num_dense_layers`` on, each
with the mixer the published list gives its own index.  At the published
depth that is every layer.  The configuration's ``num_experts`` counts the
experts held here.

As for ``nemotron_h``: the parameters come from the configuration's
``init_seed`` where it states one, and not from ``--seed``; ``--seed`` draws
the rows' order; the learning rate is ``sdar.py``'s, 3e-4 reached linearly
from zero over the configuration's ``lr_warmup_steps``.

``Family.attention_calls`` states one kind: a causal call at the layer's 32
query over 8 key heads of 64.  ``flops_per_token`` is the yardstick's own
count (``lib/cost_lfm2.py``), which a test holds equal to the program's.
"""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.lib import cost_lfm2
from benchmarks.lib.family import Family, causal
from benchmarks.models.sdar import _learning_rate
from benchmarks.reference import lfm2_moe as reference

layers_run = cost_lfm2.layers_run


def pattern(c: Dict) -> str:
    """``hybrid.py``'s letters for the layers the configuration runs."""
    return "".join({"conv": "C", "full_attention": "*"}[c["layer_types"][i]]
                   + ("D" if i < c["num_dense_layers"] else "E")
                   for i in layers_run(c))


def model_config(c: Dict, seq_len: int):
    """The published keys as ``hybrid.HybridConfig``."""
    from ray_tpu.models import hybrid

    if "C" not in hybrid.KINDS:
        raise SystemExit("this checkout's ray_tpu/models/hybrid.py has no "
                         "gated short-convolution layer kind (C): family "
                         "lfm2_moe cannot run here")
    for key, want in (("conv_bias", False), ("use_expert_bias", True),
                      ("tie_word_embeddings", True)):
        if c.get(key, want) != want:
            raise SystemExit(f"models/hybrid.py has no {key}={c[key]!r}")
    first, stop = c["experts_held"]
    if stop - first != c["num_experts"]:
        raise SystemExit(f"experts_held {c['experts_held']} is not the "
                         f"{c['num_experts']} experts num_experts counts")
    if c["hidden_size"] % c["num_attention_heads"]:
        raise SystemExit("hidden_size is no multiple of num_attention_heads")
    return hybrid, hybrid.HybridConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        pattern=pattern(c), seq_len=seq_len, rms_eps=float(c["norm_eps"]),
        n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        rope_theta=float(c["rope_theta"]), qk_norm="head",
        conv_taps=c["conv_L_cache"], dense_width=c["intermediate_size"],
        n_experts=c["num_experts_published"],
        experts_per_token=c["num_experts_per_tok"],
        d_ff=c["moe_intermediate_size"], shared_width=0,
        expert_activation="silu", gated_experts=True,
        norm_topk_prob=c["norm_topk_prob"], router_scoring="sigmoid",
        routed_scaling=float(c["routed_scaling_factor"]),
        experts_held=range(first, stop),
        router_bias_seed=c.get("router_bias_seed", 0),
        router_bias_std=c.get("router_bias_std", 0.0), tie_head=True,
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
        flops_per_token=cost_lfm2.model_flops_per_token(c, seq_len),
        attention_calls=(causal(model.n_head, model.n_kv_head,
                                model.head_dim),),
        vocab_size=c["vocab_size"], eod_id=c["eos_token_id"])
