"""A decoder that is a list of layer kinds: every layer is
``x + mixer(norm(x))`` with the mixer one of four, in an order the
configuration spells out (NVIDIA Nemotron-3-Nano's
``hybrid_override_pattern``, ``nemotron_h``; a Solar-Open2 layer, a token
mixer and then experts, is two of these):

- ``M``: a Mamba-2 mixer (``models/mamba2.py`` over ``ops/ssd.py``);
- ``K``: a KDA mixer, the gated delta rule with a per-channel decay
  (``models/kda.py`` over ``ops/kda.py``; loaded when a pattern holds it);
- ``*``: attention (``models/layers.py:attention``, the half ``llama._block``
  runs too), here without rotary embedding and without QK-norm: the
  recurrent layers carry the positions; with ``attn_gate`` an output gate
  before ``wo``;
- ``E``: a mixture of experts (``models/layers.py:feed_forward`` over
  ``models/moe.py``): sigmoid scores, a selection bias that picks the experts
  and does not weigh them, renormalised weights times
  ``routed_scaling_factor``, experts of two matrices with relu^2 (or, with
  ``gated_experts``, of three: SwiGLU), a shared expert of the same make
  beside them; ``experts_held`` says which experts this chip holds.

A chip may also hold a share of the **heads**: that is a smaller ``n_head``
/ ``n_kv_head`` / ``kda_heads`` (the matrices' columns for the heads held,
``wo``'s rows), with ``n_head_total`` stating how many the model has; what
the absent heads would add is left out, as with the experts, and no code
here differs for it.

After the last layer a final norm and an untied head; the loss is next-token
cross-entropy.  The functional contract is the other decoders':
init_params / logical_axes / loss_fn / make_train_step.

**Parameters.**  Layers of one kind share one stacked tree (``ssm``,
``kda``, ``attn``, ``experts``, each leaf with its kind's layers in front), so the
optimizer, the sharding rules and a checkpoint see a stack a kind and not
``len(pattern)`` trees.  The stack runs unrolled: layer i takes row
``pattern[:i].count(kind)`` of its kind's stack.  (A pattern that repeats
would scan over its period; the published one does not repeat evenly, and
the cut a chip trains is one stretch of it.)

**The selection bias** is no parameter: the published recipe moves it by the
load, outside the gradient.  Here it is a constant of the configuration,
drawn a layer from ``router_bias_seed`` (numpy, when the step is traced),
so that the mechanism is not a no-op at zero; no leaf holds it, so the
optimizer cannot touch it.

**What each layer keeps for the backward** (``ops/remat.py``): every layer
runs under ``jax.checkpoint`` with the one policy the rule gives this step.
The attention layers name q, k and v and the shared experts their up (and
gate) product, as ``llama.py`` names its own; the KDA layer names nothing
(what its scan keeps is bounded by the layer's own pass); the Mamba layer
names nothing (where ``ops/ssd.py`` takes its kernels the scan keeps its
inputs and each chunk's incoming state, 16 KiB a token at 64 heads of 64 over
a state of 128 in chunks of 128, from the layer's second forward to its
backward; the XLA form's intermediates are the (chunk x chunk) decays, 32 KiB
a token a float32 copy: neither is worth a rung), and the
held experts' products are recomputed inside their own backward
(``moe._held_move``).  :func:`_layer_sizes` hands the rule each kind's sizes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import mamba2
from ray_tpu.models import moe as _moe
from ray_tpu.models.layers import (attention, feed_forward, mesh_axes,
                                   rmsnorm)
from ray_tpu.ops import remat
from ray_tpu.ops.lm_head import lm_head_cross_entropy
from ray_tpu.parallel.train_state import make_optimizer  # noqa: F401
from ray_tpu.parallel.train_state import make_train_step as _make_train_step
from ray_tpu.parallel.train_state import note_first_call

#: a pattern's letters and the stack each reads
KINDS = {"M": "ssm", "*": "attn", "E": "experts", "K": "kda"}
#: what ``expert_activation`` may name
ACTIVATIONS = {"relu2": _moe.relu2, "silu": jax.nn.silu}


@dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 1024
    d_model: int = 128
    #: one letter a layer, of :data:`KINDS`
    pattern: str = "MEMEM*EME"
    seq_len: int = 128
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    logits_dtype: Any = jnp.bfloat16
    remat: bool = True
    # ``*``: what models/layers.py:attention reads
    n_head: int = 4
    n_kv_head: int = 2
    head_dim: int = 32
    #: None: no rotary embedding (nemotron_h builds none)
    rope_theta: Optional[float] = None
    qk_norm: bool = False
    block_length: int = 0
    attn_impl: str = "auto"
    #: an output gate, ``(attn * sigmoid(x wg)) wo``
    attn_gate: bool = False
    #: the query heads the model has, where ``n_head`` (and ``n_kv_head``,
    #: ``kda_heads``) are the share of them held here; None: all are held
    n_head_total: Optional[int] = None
    # ``K``: models/kda.py (its step sizes are ``time_step_*`` below)
    kda_heads: int = 4
    kda_head_dim: int = 16
    kda_chunk: int = 64
    kda_conv: int = 4
    # ``M``: models/mamba2.py
    ssm_heads: int = 8
    ssm_head_dim: int = 16
    ssm_groups: int = 2
    ssm_state: int = 16
    ssm_chunk: int = 32
    ssm_conv: int = 4
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    gate_norm_eps: float = 1e-5
    # ``E``: models/moe.py
    n_experts: int = 16
    experts_per_token: int = 2
    #: width of a routed expert, and of the shared one (0: none)
    d_ff: int = 64
    shared_width: int = 128
    #: of :data:`ACTIVATIONS`
    expert_activation: str = "relu2"
    #: experts and shared expert of three matrices, ``down(act(gate x) *
    #: up x)``, and not two, ``down(act(up x))``
    gated_experts: bool = False
    norm_topk_prob: bool = True
    router_scoring: str = "sigmoid"
    routed_scaling: float = 2.5
    #: the run of expert ids this chip holds of every ``E`` layer; None: all
    experts_held: Optional[range] = None
    router_bias_seed: int = 0
    #: spread of the selection bias's draw; 0: no bias
    router_bias_std: float = 0.0

    @property
    def held(self) -> range:
        return range(self.n_experts) if self.experts_held is None \
            else self.experts_held

    @property
    def n_layer(self) -> int:
        return len(self.pattern)

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @staticmethod
    def tiny() -> "HybridConfig":
        """Nemotron-3-Nano's shape in small: the cut's own pattern, 8 heads
        of 16 in 2 groups over a state of 16 in chunks of 32, GQA without
        rotary, experts 4-7 of 16 held, 2 a token, a shared expert."""
        return HybridConfig(experts_held=range(4, 8), router_bias_std=0.05)

    @staticmethod
    def tiny_solar() -> "HybridConfig":
        """Solar-Open2's shape in small: one period of four layers, each a
        mixer and then experts (a gated attention layer without rotary, then
        three KDA layers), heads 2-3 of 8 and experts 4-7 of 16 held, 2 a
        token, SwiGLU experts and shared expert, no selection bias."""
        return HybridConfig(
            pattern="*EKEKEKE", n_head=2, n_kv_head=1, n_head_total=8,
            attn_gate=True, kda_heads=2, kda_chunk=32,
            experts_held=range(4, 8), d_ff=48, shared_width=48,
            expert_activation="silu", gated_experts=True, routed_scaling=1.0)

    def __post_init__(self):
        assert self.pattern and set(self.pattern) <= set(KINDS), self.pattern
        held = self.held
        assert held.step == 1 and 0 <= held.start < held.stop \
            <= self.n_experts, held
        assert self.n_head % self.n_kv_head == 0
        assert self.ssm_heads % self.ssm_groups == 0
        assert 0 < self.experts_per_token <= self.n_experts
        assert self.expert_activation in ACTIVATIONS


def router_bias(config: HybridConfig, layer: int) -> Optional[np.ndarray]:
    """The selection bias of the ``layer``-th expert layer, (E,) float32: a
    function of the configuration, the same on every trace."""
    if not config.router_bias_std:
        return None
    rng = np.random.default_rng([config.router_bias_seed, layer])
    return (rng.standard_normal(config.n_experts)
            * config.router_bias_std).astype(np.float32)


def init_params(config: HybridConfig, key) -> Dict[str, Any]:
    """Matrices normal(0.02); every layer's last matrix (``out_proj``,
    ``wo``, the experts' and the shared expert's down) normal(0.02 /
    sqrt(layers)), ``rescale_prenorm_residual``."""
    D, V = config.d_model, config.vocab_size
    std = 0.02
    out_std = std / math.sqrt(config.n_layer)
    k_wte, k_head, k_ssm, k_attn, k_experts = jax.random.split(key, 5)

    def norm(key, shape, s):
        return jax.random.normal(key, shape, jnp.float32) * s

    params = {"wte": norm(k_wte, (V, D), std), "final_norm": jnp.ones((D,)),
              "lm_head": norm(k_head, (V, D), std)}
    n = config.count("M")
    if n:
        params["ssm"] = mamba2.init_params(config, k_ssm, n, out_std)
    n = config.count("*")
    if n:
        H, KV, hd = config.n_head, config.n_kv_head, config.head_dim
        ks = jax.random.split(k_attn, 4)
        params["attn"] = {
            "attn_norm": jnp.ones((n, D)),
            "wq": norm(ks[0], (n, D, H * hd), std),
            "wk": norm(ks[1], (n, D, KV * hd), std),
            "wv": norm(ks[2], (n, D, KV * hd), std),
            "wo": norm(ks[3], (n, H * hd, D), out_std),
        }
        if config.attn_gate:
            params["attn"]["wg"] = norm(jax.random.fold_in(k_attn, 4),
                                        (n, D, H * hd), std)
    n = config.count("K")
    if n:
        params["kda"] = _kda(config).init_params(
            config, jax.random.fold_in(key, 5), n, out_std)
    n = config.count("E")
    if n:
        held, F, Fs = len(config.held), config.d_ff, config.shared_width
        ks = jax.random.split(k_experts, 5)
        params["experts"] = {
            "mlp_norm": jnp.ones((n, D)),
            "router": norm(ks[0], (n, D, config.n_experts), std),
            "w_up": norm(ks[1], (n, held, D, F), std),
            "w_down": norm(ks[2], (n, held, F, D), out_std),
        }
        if Fs:
            params["experts"]["shared_up"] = norm(ks[3], (n, D, Fs), std)
            params["experts"]["shared_down"] = norm(ks[4], (n, Fs, D),
                                                    out_std)
        if config.gated_experts:
            gates = jax.random.split(jax.random.fold_in(k_experts, 5))
            params["experts"]["w_gate"] = norm(gates[0], (n, held, D, F), std)
            if Fs:
                params["experts"]["shared_gate"] = norm(gates[1], (n, D, Fs),
                                                        std)
    return params


def logical_axes(config: HybridConfig) -> Dict[str, Any]:
    L = "layers"
    axes = {"wte": ("vocab", "embed"), "final_norm": ("norm",),
            "lm_head": ("vocab", "embed")}
    if config.count("M"):
        axes["ssm"] = mamba2.logical_axes()
    if config.count("*"):
        axes["attn"] = {
            "attn_norm": (L, "norm"), "wq": (L, "embed", "heads"),
            "wk": (L, "embed", "heads"), "wv": (L, "embed", "heads"),
            "wo": (L, "heads", "embed")}
        if config.attn_gate:
            axes["attn"]["wg"] = (L, "embed", "heads")
    if config.count("K"):
        axes["kda"] = _kda(config).logical_axes()
    if config.count("E"):
        axes["experts"] = {
            "mlp_norm": (L, "norm"), "router": (L, "embed", None),
            "w_up": (L, "expert", "embed", "mlp"),
            "w_down": (L, "expert", "mlp", "embed")}
        if config.shared_width:
            axes["experts"]["shared_up"] = (L, "embed", "mlp")
            axes["experts"]["shared_down"] = (L, "mlp", "embed")
        if config.gated_experts:
            axes["experts"]["w_gate"] = (L, "expert", "embed", "mlp")
            if config.shared_width:
                axes["experts"]["shared_gate"] = (L, "embed", "mlp")
    return axes


def _kda(config: HybridConfig):
    """``models/kda.py`` where the pattern holds ``K``, else None: the module
    and ``ops/kda.py`` behind it load with the first such model."""
    if not config.count("K"):
        return None
    from ray_tpu.models import kda

    return kda


def _matmul_params(config: HybridConfig, routed: float) -> Dict[str, float]:
    """The matrix entries of a layer of each kind in the pattern, with
    ``routed`` of an expert layer's routed experts counted."""
    D, hd, kda = config.d_model, config.head_dim, _kda(config)
    w = mamba2.widths(config)
    expert_matrices = 3 if config.gated_experts else 2
    return {
        "M": D * w["in_proj"] + w["inner"] * D,
        "K": kda.matmul_params(config) if kda else 0,
        "*": D * hd * ((3 if config.attn_gate else 2) * config.n_head
                       + 2 * config.n_kv_head),
        "E": D * config.n_experts
        + expert_matrices * D * (config.shared_width + routed * config.d_ff),
    }


def params_per_layer(config: HybridConfig) -> Dict[str, int]:
    """A layer's parameters that exist here, by kind, its pre-norm included:
    of the routed experts the held ones."""
    D, kda = config.d_model, _kda(config)
    matrices = _matmul_params(config, len(config.held))
    return {
        "M": mamba2.num_params(config),
        "K": kda.num_params(config) if kda else 0,
        "*": matrices["*"] + D,
        "E": matrices["E"] + D,
    }


def num_params(config: HybridConfig) -> int:
    per_layer = params_per_layer(config)
    return 2 * config.vocab_size * config.d_model + config.d_model \
        + sum(per_layer[kind] for kind in config.pattern)


def flops_per_token(config: HybridConfig) -> float:
    """Per trained token: 6 x the matrix parameters a position meets (of the
    held experts its own, in expectation under an even router) plus causal
    attention, the state-space scan's four products a chunk (``ops/ssd.py``)
    and the delta rule's (``ops/kda.py``)."""
    S, Q = config.seq_len, min(config.ssm_chunk, config.seq_len)
    H, P, G, N = (config.ssm_heads, config.ssm_head_dim, config.ssm_groups,
                  config.ssm_state)
    met = _matmul_params(config, config.experts_per_token * len(config.held)
                         / config.n_experts)
    # a position's share of: C B^T a group (Q x Q x N), (L o C B^T)(delta x)
    # a head (Q x Q x P), both at the causal half; the chunk's state and C
    # times the incoming state a head (Q x P x N each)
    scan = 2.0 * (G * Q * N / 2 + H * Q * P / 2 + 2 * H * P * N)
    attn = 2.0 * config.n_head * config.head_dim * S  # QK^T + PV, causal
    kda = _kda(config)
    return 6.0 * (sum(met[kind] for kind in config.pattern)
                  + config.vocab_size * config.d_model) \
        + 3.0 * (config.count("M") * scan + config.count("*") * attn
                 + (config.count("K") * kda.scan_flops(config, S)
                    if kda else 0.0))


def _layer(kind: str, index: int, config: HybridConfig, axes):
    """Layer ``index`` of ``kind`` as (x, its row of the kind's stack) ->
    (x, the expert layer's counts or None)."""
    if kind == "M":
        return lambda x, blk: (mamba2.mixer(x, blk, config, axes["ssm"]),
                               None)
    if kind == "*":
        return lambda x, blk: (attention(x, blk, config, axes["attn"]), None)
    if kind == "K":
        kda = _kda(config)
        return lambda x, blk: (kda.mixer(x, blk, config, axes["kda"]), None)

    def experts(x, blk):
        x, (_, counts) = feed_forward(
            x, blk, config, axes["experts"], scoring=config.router_scoring,
            bias=router_bias(config, index), scale=config.routed_scaling,
            activation=ACTIVATIONS[config.expert_activation])
        return x, counts

    return experts


def _layer_sizes(params, x_shape, config: HybridConfig):
    """What ``ops.remat`` needs to know of ``params`` (arrays or shapes) and
    activations of ``x_shape`` (B, S, D), every size a chip's, as
    ``llama._layer_sizes`` gives them: (the ladder's candidates as (name,
    bytes), a bound on the step's own temporaries).  The candidates: q, k
    and v of the attention layers, then the shared experts' up products.
    The bound is the larger of two moments.  Inside the layers: the stacks'
    gradients in float32, every weight's cast to the compute dtype, each
    layer's kept input (and an attention layer's kernel output and
    log-sum-exp), and the widest layer's working set: for a Mamba layer six
    arrays as wide as ``in_proj``'s output and, a head and a chunk position,
    the scan's (chunk x chunk) decays, two float32 and a compute-dtype copy
    each way (the compiler fuses the rest of them away; **where the scan
    runs as ``ops/ssd_kernel.py``'s kernels they never reach HBM and the
    term overstates the layer by what it counts, 2.7 GB in the benchmark's
    cell, against 0.27 GB of boundary states: left as it is by PR 44, since
    the bound it feeds decides what ``ops/remat.py`` keeps and a change
    there is ROADMAP C15's**); for a KDA layer
    what ``kda.working_bytes`` counts a position.  Around the head:
    the logits and their cotangent beside the same casts and inputs.  Held
    against the v5e compiler for the benchmark's cell (9 layers, 2 x 8192
    tokens) it reads 8.65 GiB for 6.37 of temporaries: beside 6.21 GiB of
    state and the reserve the chip has no room for a rung, by 0.7 GiB, and
    a second trace later in the process, when 0.3 GiB more is in use, says
    the same (a bound of 7.65 kept q, k and v on the first trace and not on
    the second: PERF.md, PR 40)."""
    mesh = jax.sharding.get_abstract_mesh()
    tensor = remat.axis_shards(mesh, "tensor")
    tokens = math.prod(x_shape[:2]) // remat.axis_shards(
        mesh, "data", "fsdp", "seq")
    item = jnp.dtype(config.dtype).itemsize
    D, k = config.d_model, config.experts_per_token
    attn_width = config.n_head * config.head_dim // tensor
    qkv_width = (config.n_head + 2 * config.n_kv_head) * config.head_dim \
        // tensor
    expert_matrices = 3 if config.gated_experts else 2
    chips = jax.tree.map(
        lambda a, axes: 4 * a.size // remat.axis_shards(
            mesh, *mesh_axes(axes)), params, logical_axes(config))
    total = sum(jax.tree.leaves(chips))
    other = sum(jax.tree.leaves(
        {name: chips[name] for name in ("wte", "final_norm", "lm_head")}))
    casts = int(total * item / 4)
    kept_inputs = config.n_layer * tokens * D * item \
        + config.count("*") * tokens * (attn_width * item
                                        + config.n_head // tensor * 4)
    w, kda = mamba2.widths(config), _kda(config)
    working = {
        "M": tokens * (6 * w["in_proj"] // tensor * item
                       + config.ssm_heads // tensor
                       * min(config.ssm_chunk, x_shape[1])
                       * 2 * (2 * 4 + item)),
        "K": tokens * kda.working_bytes(config, x_shape[1], item) // tensor
        if kda else 0,
        "*": (7 if config.attn_gate else 6) * tokens * attn_width * item,
        "E": tokens * (3 * expert_matrices * config.shared_width // tensor
                       * item + 4 * k * D * item),
    }
    in_the_layers = (total - other) + casts + kept_inputs \
        + max(working[kind] for kind in set(config.pattern))
    at_the_head = other + casts + kept_inputs + 2 * tokens \
        * config.vocab_size // tensor * jnp.dtype(config.logits_dtype).itemsize
    per_kind = {remat.QKV: config.count("*") * tokens * qkv_width * item,
                remat.GATE_UP: config.count("E") * tokens
                * (expert_matrices - 1) * config.shared_width // tensor
                * item}
    return ([(name, per_kind[name]) for name in remat.LADDER],
            max(in_the_layers, at_the_head))


def forward_hidden(params: Dict[str, Any], tokens, config: HybridConfig):
    """-> (final hidden states (B, S, D), expert counts): ``moe.moe_mlp``'s
    counts with the expert layers in front, ``moe_rows`` (layers, shards,
    held) and ``moe_moved`` (layers, shards); an empty dict for a pattern
    without ``E``."""
    dt = config.dtype
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(dt)
    axes = logical_axes(config)
    policy = remat.layer_policy(*_layer_sizes(params, x.shape, config)) \
        if config.remat else None
    seen = dict.fromkeys(KINDS, 0)
    counts = []
    for kind in config.pattern:
        index, seen[kind] = seen[kind], seen[kind] + 1
        layer = _layer(kind, index, config, axes)
        if config.remat:
            layer = jax.checkpoint(layer, policy=policy)
        x, counted = layer(x, jax.tree.map(lambda a: a[index],
                                           params[KINDS[kind]]))
        if counted is not None:
            counts.append(counted)
    with jax.named_scope("lm_head"):
        x = rmsnorm(x, params["final_norm"], config.rms_eps).astype(dt)
    return x, {name: jnp.stack([c[name] for c in counts])
               for name in (counts[0] if counts else ())}


def loss_fn(params, tokens, targets, config: HybridConfig):
    """Mean next-token cross-entropy of ``tokens`` against ``targets``; the
    configuration names no auxiliary loss and none is added."""
    return loss_and_counters(params, tokens, targets, config)[0]


def loss_and_counters(params, tokens, targets, config: HybridConfig):
    """-> (:func:`loss_fn`'s scalar, the step counters of
    ``tracing.STEP_COUNTER_REGISTRY`` the expert layers leave)."""
    S = tokens.shape[1]
    if config.count("M"):
        chunk = min(config.ssm_chunk, S)
        note_first_call(ssm_heads=config.ssm_heads,
                        ssm_state=config.ssm_state, ssm_chunk=chunk,
                        ssm_chunks=tokens.shape[0] * S // chunk)
    if config.count("K"):
        chunk = min(config.kda_chunk, S)
        note_first_call(kda_heads=config.kda_heads,
                        kda_head_dim=config.kda_head_dim, kda_chunk=chunk,
                        kda_chunks=tokens.shape[0] * S // chunk,
                        heads_held=config.n_head,
                        heads_total=config.n_head_total or config.n_head,
                        attn_gate=config.attn_gate)
    note_first_call(layer_kinds=config.pattern,
                    experts_held=len(config.held),
                    experts_total=config.n_experts,
                    router_scoring=config.router_scoring,
                    attn_positions=S, loss_positions=S)
    x, counts = forward_hidden(params, tokens, config)
    with jax.named_scope("lm_head"):
        ce = lm_head_cross_entropy(x, params["lm_head"].astype(config.dtype),
                                   targets, config.logits_dtype, None)
    return ce, counts


def make_train_step(config: HybridConfig, optimizer):
    """Pure (params, opt_state, tokens, targets) -> (params, opt_state, loss):
    parallel.train_state.make_train_step over this model's loss; the expert
    layers' ``moe_rows`` and ``moe_moved`` leave through ``step.counters``."""
    if config.count("E"):
        return _make_train_step(partial(loss_and_counters, config=config),
                                optimizer, has_counters=True)
    return _make_train_step(partial(loss_fn, config=config), optimizer)
