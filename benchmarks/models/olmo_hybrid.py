"""Family adapter: allenai's Olmo-Hybrid published ``config.json``
(``olmo_hybrid``; Olmo-Hybrid-7B) through ``ray_tpu/models/hybrid.py``.  A
layer is a token mixer and then a dense SwiGLU MLP, two of ``hybrid.py``'s
single-mixer layers: layer i is ``G`` (the gated delta rule with one decay a
head, keys narrower than values) where ``layer_types`` says
``linear_attention`` and ``*`` (attention without rotary embedding, an
RMSNorm over all of q and of k) where it says ``full_attention``, then ``D``,
every sub-layer under ``norm_after``.  ``layer_types`` is the published
list, whole, read by index: a cut of ``num_hidden_layers`` n is its first n
layers.

The first dense model on this path: no router, so no ``init_seed``: the
parameters come from ``--seed``, as the Llama families' do, and the learning
rate is theirs, 3e-4, reached linearly from zero over the configuration's
``lr_warmup_steps`` (``sdar.py``'s schedule; 0: from the first step).

``Family.attention_calls`` states one kind: a causal call at the layer's
query and key heads of ``hidden_size / num_attention_heads``.
``flops_per_token`` is the yardstick's own count
(``lib/cost_olmo_hybrid.py``), which a test holds equal to the program's.
"""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.lib import cost_olmo_hybrid
from benchmarks.lib.family import Family, causal
from benchmarks.models.sdar import _learning_rate
from benchmarks.reference import olmo_hybrid as reference


def pattern(c: Dict) -> str:
    """``hybrid.py``'s letters for the layers the configuration runs."""
    return "".join(cost_olmo_hybrid.KINDS[kind] + "D" for kind in
                   c["layer_types"][:c["num_hidden_layers"]])


def model_config(c: Dict, seq_len: int):
    """The published keys as ``hybrid.HybridConfig``."""
    from ray_tpu.models import hybrid

    if "G" not in hybrid.KINDS:
        raise SystemExit("this checkout's ray_tpu/models/hybrid.py has no "
                         "gated-delta-net layer kind (G) and no norm_after: "
                         "family olmo_hybrid cannot run here")
    for key, want in (("linear_allow_neg_eigval", True),
                      ("tie_word_embeddings", False),
                      ("attention_bias", False), ("hidden_act", "silu"),
                      ("rope_parameters", {"rope_theta": None})):
        if c[key] != want:
            raise SystemExit(f"models/hybrid.py has no {key}={c[key]!r}")
    if c["linear_num_key_heads"] != c["linear_num_value_heads"]:
        raise SystemExit("models/gdn.py has as many key heads as value "
                         "heads: no linear_num_key_heads="
                         f"{c['linear_num_key_heads']} under "
                         f"{c['linear_num_value_heads']} value heads")
    if c["hidden_size"] % c["num_attention_heads"]:
        raise SystemExit("hidden_size is no multiple of num_attention_heads")
    return hybrid, hybrid.HybridConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        pattern=pattern(c), seq_len=seq_len,
        rms_eps=float(c["rms_norm_eps"]), norm_after=True,
        n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        rope_theta=None, qk_norm=True,
        gdn_heads=c["linear_num_value_heads"],
        gdn_key_dim=c["linear_key_head_dim"],
        gdn_value_dim=c["linear_value_head_dim"],
        gdn_conv=c["linear_conv_kernel_dim"], gdn_chunk=c["gdn_chunk"],
        time_step_min=c["time_step_min"], time_step_max=c["time_step_max"],
        time_step_floor=c["time_step_floor"],
        dense_width=c["intermediate_size"], **c.get("options", {}))


def build(config_file: Dict, seq_len: int) -> Family:
    c = config_file
    hybrid, model = model_config(c, seq_len)
    return Family(
        init_fn=functools.partial(hybrid.init_params, model),
        logical_axes=hybrid.logical_axes(model),
        make_optimizer=lambda: hybrid.make_optimizer(
            learning_rate=_learning_rate(c.get("lr_warmup_steps", 0))),
        make_train_step=functools.partial(hybrid.make_train_step, model),
        loss_fn=lambda p, t, y: hybrid.loss_fn(p, t, y, model),
        reference_loss=lambda p, t, y, q_block: reference.loss(
            p, t, y, c, q_block=q_block),
        flops_per_token=cost_olmo_hybrid.model_flops_per_token(c, seq_len),
        attention_calls=(causal(model.n_head, model.n_kv_head,
                                model.head_dim),),
        vocab_size=c["vocab_size"], eod_id=c["eos_token_id"])
