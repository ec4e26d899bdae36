"""A decoder that is a list of layer kinds: every layer is
``x + mixer(norm(x))``, or with ``norm_after`` (the Olmo 2 / Olmo 3
family's block) ``x + norm(mixer(x))``: the kind's one norm weight
multiplies the sub-layer's output before the residual add and the sub-layer
reads ``x`` un-normed.  The mixer is one of :data:`KINDS`, in an order the
configuration spells out (NVIDIA Nemotron-3-Nano's
``hybrid_override_pattern``, ``nemotron_h``; a Solar-Open2 layer, a token
mixer and then experts, is two of these):

- ``M``: a Mamba-2 mixer (``models/mamba2.py`` over ``ops/ssd.py``);
- ``K``: a KDA mixer, the gated delta rule with a per-channel decay
  (``models/kda.py`` over ``ops/kda.py``);
- ``*``: attention (``models/attn.py``), here without rotary embedding and
  without QK-norm: the recurrent layers carry the positions;
- ``E``: a mixture of experts (``models/experts.py`` over ``models/moe.py``);
- ``L``: latent attention (``models/mla.py``): queries and keys from two
  normed low-rank latents, one rotary key for all heads, a value head
  narrower than the q.k head;
- ``D``: a dense SwiGLU MLP (``models/dense.py``): the leading layers of a
  model whose later layers hold experts, or every layer's feed-forward part
  in a model without experts;
- ``W``: attention over a causal band (``models/window.py`` over
  ``models/attn.py``), beside ``*`` in a stack whose full and window layers
  differ in head count and rotary settings (the ``laguna`` family's
  ``layer_types``);
- ``C``: a gated short convolution (``models/shortconv.py``): a causal
  depthwise convolution of ``conv_taps`` taps between two element-wise
  gates, the token mixer of three layers in four of the ``lfm2_moe``
  family, whose fourth is ``*`` with ``rope_theta`` and ``qk_norm``;
- ``G``: a gated-delta-net mixer, the gated delta rule with one decay a
  head and keys narrower than values (``models/gdn.py`` over
  ``ops/gdn.py``), the ``linear_attention`` layers of the ``olmo_hybrid``
  family, whose fourth layer is ``*`` without rotary embedding and with a
  QK-norm over all heads, every layer under ``norm_after``.

**A kind is a module** and one line of :data:`KINDS`; this file knows no
kind by name.  The module (loaded with the first pattern that holds its
letter) answers, for a configuration with the fields it reads:

- ``init_params(config, key, n, out_std)``: ``n`` layers' parameters stacked
  on a leading axis, the layer's last matrix normal(``out_std``);
  ``logical_axes(config)``: the leaves' logical axes, `layers` first;
- ``matmul_params(config, routed)``: the matrix entries a position meets
  (``routed``: how many of an expert layer's routed experts to count);
  ``num_params(config)``: a layer's parameters that exist here, its
  pre-norm included; ``mixer_flops(config, seq_len)``: the forward FLOPs a
  position that are no matrix it meets (causal attention, a scan);
- ``layer_bytes(config, tokens, seq_len, tensor, itemsize)``: a chip's bytes
  of one layer for :func:`_layer_sizes`, under the implementation its ops
  pick for these shapes and the ambient mesh (``ops.ssd.path`` and its
  like): (its working set, what it keeps for the backward beside its input,
  {a name of ``ops.remat``'s it bears: (the bytes, the forward work keeping
  them spares, ``remat.spared``)}; ``ROUTING`` spares nothing, it is kept);
- ``first_call_facts(config, rows, seq_len)``: what it notes for the
  first-call record (``util/first_call.py``);
- ``layer(config, axes, index)``: layer ``index`` of the kind as (x, its row
  of the stack) -> (x, the step counters it leaves or None);
- ``NORM_AFTER = True`` where the kind reads ``norm_after`` (``*``, ``D``,
  ``G``); a configuration that sets it over any other kind is refused, so
  no kind grows a path no model uses;
- ``branch(config, axes, index)`` where the kind has its layer without the
  residual add too (``L``, ``D``, ``E``), as (u, its row of the stack) ->
  (f(norm(u)), the step counters or None): what a residual of several
  streams runs (below); a configuration with ``streams`` > 1 over any other
  kind is refused likewise.

**A residual of several streams** (``streams`` n > 1: manifold-constrained
hyper-connections, ``models/streams.py``): the embedding is copied to n
streams, every sub-layer reads one mix of them and writes its branch's
output back to all under maps of its own (one more stack, ``hc``, a row a
sub-layer, the prediction module's after the pattern's), and after the last
layer the streams are summed for the final norm.  The residual add is then
the write's and no kind's; with ``streams`` 1 none of it is traced and the
add is each kind's own ``x + f(norm(x))``.  A prediction module reads the
streams one by one (one ``hidden_norm``, one ``w_eh``), runs its block under
maps too and sums the streams before its final norm.

A chip may also hold a share of the **heads**: that is a smaller ``n_head``
/ ``n_kv_head`` / ``kda_heads`` (the matrices' columns for the heads held,
``wo``'s rows), with ``n_head_total`` stating how many the model has; what
the absent heads would add is left out, as with the experts, and no code
here differs for it.

After the last layer a final norm and an untied head, or with ``tie_head``
the embedding as the head: no ``lm_head`` leaf, ``wte`` read by the gather
and by the head's product (and by a prediction module's), its gradient the
sum of the two.  The loss is next-token cross-entropy.  The functional
contract is the other decoders': init_params / logical_axes / loss_fn /
make_train_step.

**A multi-token-prediction module** (``mtp_depth`` 1; the ``deepseek_v3``
family's) is a second pass after the last layer: each position's hidden
state, before the final norm, is joined with the embedding of the token
after it (two norms, then ``w_eh`` over the two side by side, the embedding
first), runs one more of the model's last layer (the pattern's last two
kinds: a mixer and what follows it), causal over the row, and reads the same
head, after a final norm of its own, for the token two ahead.  The step's
loss is ``loss_main + mtp_weight x loss_mtp``, and the two leave as step
counters.  Its parameters: ``mtp`` (the three norms and ``w_eh``) and one
more row of its kinds' stacks.  A configuration without one (``mtp_depth``
0) traces none of it.

**Parameters.**  Layers of one kind share one stacked tree (``ssm``,
``kda``, ``attn``, ``experts``, ``mla``, ``dense``, ``window``,
``shortconv``, ``gdn``, each leaf with its kind's layers in front, the
prediction module's after the pattern's; under ``streams`` > 1 also ``hc``,
a row a sub-layer), so the
optimizer, the sharding rules and a checkpoint see a stack a kind and not
``len(pattern)`` trees.  The stack runs unrolled: layer i takes row
``pattern[:i].count(kind)`` of its kind's stack.  (A pattern that repeats
would scan over its period; the published one does not repeat evenly, and
the cut a chip trains is one stretch of it.)

**What each layer keeps for the backward** (``ops/remat.py``): every layer
runs under a ``jax.checkpoint`` of its own, with the policy the rule's
decision gives that layer (``Decision.policy``): the kinds name the arrays
worth keeping, as ``llama.py`` names its own, :func:`_layer_sizes` hands
the rule each kind's rungs (bytes and spared work a layer, how many layers
name them), and a rung is kept for as many of its kind's layers as fit, the
first ones.  Beside its input and the splash kernel's residuals a layer
always keeps what its router decided (``remat.ROUTING``,
``models/moe.py``): a step routes once.
"""

from __future__ import annotations

import importlib
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import streams
from ray_tpu.models.layers import Yarn, mesh_axes, rmsnorm
from ray_tpu.ops import remat
from ray_tpu.ops.lm_head import lm_head_cross_entropy
from ray_tpu.parallel.train_state import make_optimizer  # noqa: F401
from ray_tpu.parallel.train_state import make_train_step as _make_train_step
from ray_tpu.util import first_call
from ray_tpu.util.tracing import step_counter


@dataclass(frozen=True)
class Kind:
    """An entry of :data:`KINDS`."""
    #: the name of the kind's stack in the parameter tree
    stack: str
    #: the module with the kind's interface, or its dotted name
    source: Any
    #: which key its parameters are drawn from: 2 to 4 the third to fifth of
    #: the five keys ``init_params`` splits its own into (the embedding and
    #: the head take the first two), 5 and up that number folded into it
    draw: int

    @property
    def module(self):
        return importlib.import_module(self.source) \
            if isinstance(self.source, str) else self.source


#: a pattern's letters
KINDS = {"M": Kind("ssm", "ray_tpu.models.mamba2", 2),
         "*": Kind("attn", "ray_tpu.models.attn", 3),
         "E": Kind("experts", "ray_tpu.models.experts", 4),
         "K": Kind("kda", "ray_tpu.models.kda", 5),
         "L": Kind("mla", "ray_tpu.models.mla", 6),
         "D": Kind("dense", "ray_tpu.models.dense", 7),
         "W": Kind("window", "ray_tpu.models.window", 8),
         "C": Kind("shortconv", "ray_tpu.models.shortconv", 9),
         "G": Kind("gdn", "ray_tpu.models.gdn", 10)}

#: What the compiler's heap loses between buffers of different lifetimes (a
#: layer's dq partials and products against the long-lived gradients and kept
#: inputs), a sub-layer, over the fullest moment :func:`_layer_sizes` adds
#: up: the seven hybrid cells' compiled steps read 3 to 28 MiB a sub-layer
#: over that moment (PERF.md, PR 63), the cells of fourteen most in all.
PACKING = 40 << 20

#: folded into ``init_params``' key for the prediction module's ``w_eh``
MTP_DRAW = 47


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
    # ``*``: models/attn.py, what models/layers.py:attention reads
    n_head: int = 4
    n_kv_head: int = 2
    head_dim: int = 32
    #: None: no rotary embedding (nemotron_h builds none)
    rope_theta: Optional[float] = None
    #: ``"head"``: an RMSNorm with a weight over each head's lanes of q and
    #: of k, before the rotary pass; True: over all of a layer's heads
    qk_norm: Any = False
    block_length: int = 0
    attn_impl: str = "auto"
    #: an output gate, ``(attn * sigmoid(x wg)) wo``: True a channel each,
    #: ``"head"`` a scalar a head
    attn_gate: Any = False
    #: the keys a query reads, its own among them; 0: every earlier one
    attn_window: int = 0
    #: how many of a head's first lanes rotate; None: all
    rope_rotary: Optional[int] = None
    #: ``models/layers.py:Yarn``: the rotary frequencies' stretch; None: none
    rope_yarn: Optional[Yarn] = None
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
    # ``E``: models/experts.py
    n_experts: int = 16
    experts_per_token: int = 2
    #: width of a routed expert, and of the shared one (0: none)
    d_ff: int = 64
    shared_width: int = 128
    #: of ``models/experts.py``'s ``ACTIVATIONS``
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
    # ``L``: models/mla.py
    mla_heads: int = 4
    #: the width of the query's and of the keys' and values' latent
    mla_q_latent: int = 48
    mla_kv_latent: int = 32
    #: a q.k head: its lanes without position, then its rotary lanes
    mla_nope_dim: int = 16
    mla_rope_dim: int = 8
    mla_v_dim: int = 16
    mla_rope_theta: float = 10000.0
    #: the rotary pairs are lanes (2i, 2i + 1), not (i, i + half)
    mla_rope_interleave: bool = True
    #: ``models/layers.py:Yarn``: the rotary lanes' stretch; None: none
    mla_rope_yarn: Optional[Yarn] = None
    #: the softmax's scale; None: ``(mla_nope_dim + mla_rope_dim) ** -0.5``
    mla_sm_scale: Optional[float] = None
    # ``D``: models/dense.py
    dense_width: int = 256
    # ``W``: models/window.py, in the place of ``*``'s ``n_head``,
    # ``attn_window`` and ``rope_theta`` (the whole head rotates, no YaRN)
    window_heads: int = 4
    window_keys: int = 16
    window_rope_theta: Optional[float] = 10000.0
    # ``C``: models/shortconv.py, ``d_model`` wide
    conv_taps: int = 3
    # ``G``: models/gdn.py (its step sizes are ``time_step_*`` above)
    gdn_heads: int = 4
    gdn_key_dim: int = 12
    gdn_value_dim: int = 24
    gdn_chunk: int = 64
    gdn_conv: int = 4
    #: every layer is ``x + norm(f(x))`` and not ``x + f(norm(x))``: the
    #: family's block, read by the kinds that state ``NORM_AFTER``
    norm_after: bool = False
    #: the head is the embedding: no ``lm_head`` leaf
    tie_head: bool = False
    #: multi-token-prediction modules after the last layer (0 or 1) and the
    #: weight of a module's loss in the step's
    mtp_depth: int = 0
    mtp_weight: float = 0.3
    #: the residual's streams (``models/streams.py``); 1: the plain residual
    streams: int = 1
    #: the turns that normalise a sub-layer's stream map, the eps added to a
    #: row's or column's sum, and the clamp on its logits before ``exp``
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)

    @property
    def held(self) -> range:
        return range(self.n_experts) if self.experts_held is None \
            else self.experts_held

    @property
    def n_layer(self) -> int:
        return len(self.pattern)

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def mtp_kinds(self) -> str:
        """The kinds of the prediction module's layers, one more of the
        model's last layer (a mixer and what follows it: the pattern's last
        two kinds); empty without one."""
        return self.pattern[-2:] * self.mtp_depth

    def rows(self, kind: str) -> int:
        """Of the kind's stack: the pattern's layers, then the module's."""
        return self.count(kind) + self.mtp_kinds.count(kind)

    @property
    def sublayers(self) -> str:
        """Every layer that runs, one letter each: the pattern's, then the
        prediction module's."""
        return self.pattern + self.mtp_kinds

    def __post_init__(self):
        assert self.pattern and set(self.pattern) <= set(KINDS), self.pattern
        if self.norm_after:
            deaf = [kind for kind, entry in _kinds(self).items()
                    if not getattr(entry.module, "NORM_AFTER", False)]
            if deaf:
                raise ValueError(
                    f"norm_after over the kinds {deaf} of pattern "
                    f"{self.pattern!r}: their modules do not read it")
        assert self.mtp_depth in (0, 1), self.mtp_depth
        assert self.streams >= 1, self.streams
        if self.streams > 1:
            whole = [kind for kind, entry in _kinds(self).items()
                     if not hasattr(entry.module, "branch")]
            if whole:
                raise ValueError(
                    f"streams {self.streams} over the kinds {whole} of "
                    f"pattern {self.pattern!r}: their modules have no "
                    "branch without the residual add")
        held = self.held
        assert held.step == 1 and 0 <= held.start < held.stop \
            <= self.n_experts, held
        assert self.n_head % self.n_kv_head == 0
        assert self.ssm_heads % self.ssm_groups == 0
        assert 0 < self.experts_per_token <= self.n_experts


def _kinds(config: HybridConfig) -> Dict[str, Kind]:
    """The pattern's kinds, each once, in the order of :data:`KINDS`."""
    return {kind: entry for kind, entry in KINDS.items()
            if kind in config.pattern}


def init_params(config: HybridConfig, key) -> Dict[str, Any]:
    """Matrices normal(0.02); every layer's last matrix (``out_proj``,
    ``wo``, the experts' and the shared expert's down) normal(0.02 /
    sqrt(layers)), ``rescale_prenorm_residual``."""
    D, V = config.d_model, config.vocab_size
    std = 0.02
    out_std = std / math.sqrt(config.n_layer)
    keys = jax.random.split(key, 5)

    def norm(key, shape, s):
        return jax.random.normal(key, shape, jnp.float32) * s

    params = {"wte": norm(keys[0], (V, D), std), "final_norm": jnp.ones((D,))}
    if not config.tie_head:
        params["lm_head"] = norm(keys[1], (V, D), std)
    for kind, entry in _kinds(config).items():
        params[entry.stack] = entry.module.init_params(
            config, keys[entry.draw] if entry.draw < len(keys)
            else jax.random.fold_in(key, entry.draw),
            config.rows(kind), out_std)
    if config.mtp_depth:
        params["mtp"] = {
            "embed_norm": jnp.ones((D,)), "hidden_norm": jnp.ones((D,)),
            "w_eh": norm(jax.random.fold_in(key, MTP_DRAW), (2 * D, D), std),
            "final_norm": jnp.ones((D,))}
    if config.streams > 1:
        params[streams.STACK] = streams.init_params(
            config, jax.random.fold_in(key, streams.DRAW),
            len(config.sublayers))
    return params


def logical_axes(config: HybridConfig) -> Dict[str, Any]:
    axes = {"wte": ("vocab", "embed"), "final_norm": ("norm",)}
    if not config.tie_head:
        axes["lm_head"] = ("vocab", "embed")
    for entry in _kinds(config).values():
        axes[entry.stack] = entry.module.logical_axes(config)
    if config.mtp_depth:
        axes["mtp"] = {"embed_norm": ("norm",), "hidden_norm": ("norm",),
                       "w_eh": ("embed", None), "final_norm": ("norm",)}
    if config.streams > 1:
        axes[streams.STACK] = streams.logical_axes(config)
    return axes


def num_params(config: HybridConfig) -> int:
    D = config.d_model
    return (1 if config.tie_head else 2) * config.vocab_size * D + D \
        + sum(KINDS[kind].module.num_params(config)
              for kind in config.sublayers) \
        + config.mtp_depth * (2 * D * D + 3 * D) \
        + (config.streams > 1) * len(config.sublayers) \
        * streams.num_params(config)


def flops_per_token(config: HybridConfig) -> float:
    """Per trained token: 6 x the matrix parameters a position meets (of the
    held experts its own, in expectation under an even router) plus 3 x what
    each layer's mixer adds beside them (causal attention, the state-space
    scan's products, either delta rule's, a short convolution's taps and
    gates).  The head counts once, tied or not: the embedding is a gather.
    A prediction module adds its block's layers, ``w_eh`` and a second pass
    through the head.  Under ``streams`` n > 1 every sub-layer adds its
    maps' product, and the module's ``w_eh`` meets the embedding's half once
    and the hidden state's half a stream, ``(1 + n) D^2``."""
    routed = config.experts_per_token * len(config.held) / config.n_experts
    layers = [KINDS[kind].module for kind in config.sublayers]
    D, n = config.d_model, config.streams
    maps = len(layers) * streams.matmul_params(config) if n > 1 else 0
    return 6.0 * (sum(m.matmul_params(config, routed) for m in layers)
                  + (1 + config.mtp_depth) * config.vocab_size * D
                  + config.mtp_depth * (1 + n) * D * D + maps) \
        + 3.0 * sum(m.mixer_flops(config, config.seq_len) for m in layers)


def _layer_sizes(params, x_shape, config: HybridConfig):
    """What ``ops.remat`` needs to know of ``params`` (arrays or shapes) and
    activations of ``x_shape`` (B, S, D), every size a chip's: (the rungs
    the layers name, ``remat.Rung``s, one for each kind and name with the
    number of the kind's layers, and behind them, where a layer routes,
    ``ROUTING`` likewise; a bound on the step's own temporaries without any
    of them).  The bound is the fullest moment of the step, and the step
    runs unrolled, so the moments are the layers' own: while layer i runs
    backwards the gradients of the layers after it stand in float32 (and
    the head's and the embedding's), the layers up to it still hold their
    kept input and what their kinds keep beside it (an expert layer its
    held matrices in the compute dtype), and layer i has its working set,
    each kind's as its ``layer_bytes`` states it for the path its ops take
    here (the kernels' where they run: boundary states and the scan's
    inputs, not XLA's (chunk x chunk) arrays).  Before the first of them,
    around the head: every layer's kept arrays, the logits and their
    cotangent and the head's gradient.  After the last: every gradient.
    Over the fullest of them :data:`PACKING` a sub-layer.
    Held against the v5e compiler's
    ``memory_analysis()`` for the seven hybrid cells it lies over the
    compiled step's temporaries by less than 0.5 GiB
    (``tests/test_remat.py::test_the_bound_lies_over_the_compilers``).
    Under ``streams`` n > 1 a kept input is n times as wide, the widest
    working set may be the maps' own, and the maps (``remat.MAPS``) are a
    rung of the sub-layers' own group."""
    mesh = jax.sharding.get_abstract_mesh()
    tensor = remat.axis_shards(mesh, "tensor")
    tokens = math.prod(x_shape[:2]) // remat.axis_shards(
        mesh, "data", "fsdp", "seq")
    item = jnp.dtype(config.dtype).itemsize
    chips = jax.tree.map(
        lambda a, axes: 4 * a.size // remat.axis_shards(
            mesh, *mesh_axes(axes)), params, logical_axes(config))
    kinds = _kinds(config)
    total = sum(jax.tree.leaves(chips))
    layers = config.sublayers
    # a group of layers: (how many they are, their layer_bytes)
    groups = {kind: (layers.count(kind), entry.module.layer_bytes(
        config, tokens, x_shape[1], tensor, item))
        for kind, entry in kinds.items()}
    maps = (0, 0, {})
    if config.streams > 1:
        maps = streams.layer_bytes(config, tokens, item)
        groups = {streams.STACK: (len(layers), maps), **groups}
    rungs = [remat.Rung(name, nbytes, spares, n, group)
             for group, (n, (_, _, named)) in groups.items()
             for name, (nbytes, spares) in named.items()]
    # the routing last, where the rule's callers look for it
    rungs.sort(key=lambda rung: rung.name == remat.ROUTING)

    hc = sum(jax.tree.leaves(chips.get(streams.STACK, {}))) // len(layers)
    x = config.streams * tokens * config.d_model * item
    # a kind's layer: (its gradients, what it keeps, its working set)
    a_layer = {kind: (sum(jax.tree.leaves(chips[kinds[kind].stack]))
                      // config.rows(kind) + hc, x + kept + maps[1],
                      working + maps[0])
               for kind, (_, (working, kept, _)) in groups.items()
               if kind in kinds}
    grads, kept, working = zip(*(a_layer[kind] for kind in layers))
    outside = total - sum(grads)
    moments = [  # with a prediction module the first pass's logits wait
        sum(kept) + outside // (1 if config.tie_head else 2)
        + (2 + config.mtp_depth) * tokens * config.vocab_size // tensor
        * jnp.dtype(config.logits_dtype).itemsize,
        total]
    for i in range(len(layers)):
        moments.append(outside + sum(grads[i + 1:]) + sum(kept[:i + 1])
                       + working[i])
    return rungs, max(moments) + len(layers) * PACKING


def _placed(kinds: str) -> List[Tuple[str, int]]:
    """``kinds`` as (kind, its row of the kind's stack)."""
    seen: Dict[str, int] = {}
    placed = []
    for kind in kinds:
        placed.append((kind, seen.get(kind, 0)))
        seen[kind] = placed[-1][1] + 1
    return placed


def _run(params, x, layers, config: HybridConfig, axes, decision,
         first: int = 0):
    """``x`` through ``layers`` (:func:`_placed`'s pairs) -> (x, the step
    counters of the layers that leave some, in order), each layer under a
    ``jax.checkpoint`` whose policy is ``decision``'s for that layer
    (``remat.Decision``; None: no checkpoint).  Under ``streams``
    > 1 ``x`` is the streams, n arrays (B, S, D), each layer its kind's branch
    between the maps' read and write (``streams.layer``), and ``first`` the
    row of ``hc`` that the first of ``layers`` takes."""
    counts = []
    for at, (kind, index) in enumerate(layers, first):
        stack, module = KINDS[kind].stack, KINDS[kind].module
        rows = [jax.tree.map(lambda a: a[index], params[stack])]
        bears = [(kind, index)]
        if config.streams > 1:
            layer = streams.layer(
                config, module.branch(config, axes[stack], index))
            rows.append(jax.tree.map(lambda a: a[at], params[streams.STACK]))
            bears.append((streams.STACK, at))
        else:
            layer = module.layer(config, axes[stack], index)
        if decision is not None:
            layer = jax.checkpoint(layer, policy=decision.policy(*bears))
        x, counted = layer(x, *rows)
        if counted is not None:
            counts.append(counted)
    return x, counts


def _stacked(counts) -> Dict[str, Any]:
    """The layers' counters, each stacked over the layers that leave it; an
    empty dict where none does."""
    names = dict.fromkeys(name for counted in counts for name in counted)
    return {name: jnp.stack([c[name] for c in counts if name in c])
            for name in names}


def _head(params, config: HybridConfig):
    """The (V, D) matrix the logits are made with."""
    return params["wte" if config.tie_head else "lm_head"]


def _predict_ahead(params, x, targets, run, config: HybridConfig):
    """The prediction module over the last layer's output ``x`` (before the
    final norm) and the row's ``targets`` (position i's is the token after
    it) -> (the mean over i < S - 1 of the cross-entropy of the token two
    ahead, the module's layers' counters in order).  ``run``: a row's
    activations through the module's layers (:func:`_run` with the step's
    axes and decision).  The last position, which has no token two ahead,
    goes through the block and weighs nothing.  Under ``streams`` > 1 ``x``
    is the streams: each is normed and joined with the embedding by the one
    ``w_eh`` (whose embedding half multiplies once for all of them, the
    product of the two side by side written as the sum of the halves'), the
    block runs under maps of its own, and the streams are summed before the
    module's final norm."""
    dt = config.dtype
    mtp = params["mtp"]
    B, S = targets.shape
    with jax.named_scope("mtp"):
        ahead = rmsnorm(params["wte"][targets].astype(dt), mtp["embed_norm"],
                        config.rms_eps).astype(dt)
        if config.streams > 1:
            first, second = jnp.split(mtp["w_eh"].astype(dt), 2)
            once = jnp.matmul(ahead, first,
                              preferred_element_type=jnp.float32)
            z = tuple((once + jnp.matmul(
                rmsnorm(stream, mtp["hidden_norm"],
                        config.rms_eps).astype(dt), second,
                preferred_element_type=jnp.float32)).astype(dt)
                for stream in x)
        else:
            here = rmsnorm(x, mtp["hidden_norm"], config.rms_eps).astype(dt)
            z = jnp.concatenate([ahead, here], axis=-1) \
                @ mtp["w_eh"].astype(dt)
    z, counts = run(z)
    with jax.named_scope("mtp_head"):
        if config.streams > 1:
            z = sum(stream.astype(jnp.float32) for stream in z)
        z = rmsnorm(z, mtp["final_norm"], config.rms_eps).astype(dt)
        weights = jnp.broadcast_to(
            (jnp.arange(S) < S - 1) / (B * (S - 1)), (B, S))
        ce = lm_head_cross_entropy(
            z, _head(params, config).astype(dt),
            jnp.roll(targets, -1, axis=1),
            config.logits_dtype, weights)
    return ce, counts


def loss_fn(params, tokens, targets, config: HybridConfig):
    """Mean next-token cross-entropy of ``tokens`` against ``targets`` (with
    a prediction module, plus ``mtp_weight`` times its own); the
    configuration names no auxiliary loss and none is added."""
    return loss_and_counters(params, tokens, targets, config)[0]


def loss_and_counters(params, tokens, targets, config: HybridConfig):
    """-> (:func:`loss_fn`'s scalar, the step counters of
    ``tracing.STEP_COUNTER_REGISTRY`` the step leaves): the layers', each
    stacked over the layers that leave it (the expert layers' ``moe_rows``
    (layers, shards, held) and ``moe_moved`` (layers, shards), the
    prediction module's layer last; none for a pattern whose kinds leave
    none), and with a prediction module the two losses the scalar sums,
    ``loss_main`` and ``loss_mtp``; under ``streams`` > 1 every sub-layer's
    ``mhc_sinkhorn_err``, (sub-layers,), the module's last."""
    rows, S = tokens.shape
    dt = config.dtype
    first_call.note(layer_kinds=config.pattern, loss_positions=S)
    for entry in _kinds(config).values():
        first_call.note(**entry.module.first_call_facts(config, rows, S))
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(dt)
    decision = remat.decide(*_layer_sizes(params, x.shape, config)) \
        if config.remat else None
    run = partial(_run, params, config=config, axes=logical_axes(config),
                  decision=decision)
    placed = _placed(config.sublayers)
    if config.streams > 1:
        first_call.note(**streams.first_call_facts(config, len(placed)))
        x = (x,) * config.streams
    x, counts = run(x, placed[:config.n_layer])
    with jax.named_scope("lm_head"):
        last = x if config.streams == 1 \
            else sum(stream.astype(jnp.float32) for stream in x)
        hidden = rmsnorm(last, params["final_norm"],
                         config.rms_eps).astype(dt)
    # between the norm and the head, where the older steps' text has it
    counts = _stacked(counts)
    with jax.named_scope("lm_head"):
        loss = lm_head_cross_entropy(
            hidden, _head(params, config).astype(dt), targets,
            config.logits_dtype, None)
    if config.mtp_depth:
        first_call.note(mtp_depth=config.mtp_depth,
                        mtp_weight=config.mtp_weight)
        ahead, more = _predict_ahead(
            params, x, targets, partial(run, layers=placed[config.n_layer:],
                                        first=config.n_layer), config)
        for name, rows in _stacked(more).items():
            counts[name] = jnp.concatenate([counts[name], rows])
        counts.update({step_counter("loss_main"): loss,
                       step_counter("loss_mtp"): ahead})
        loss = loss + config.mtp_weight * ahead
    return loss, counts


def make_train_step(config: HybridConfig, optimizer):
    """Pure (params, opt_state, tokens, targets) -> (params, opt_state, loss):
    parallel.train_state.make_train_step over this model's loss; the layers'
    counters (the expert layers' ``moe_rows`` and ``moe_moved``, the stream
    maps' ``mhc_sinkhorn_err``) leave through ``step.counters``."""
    return _make_train_step(partial(loss_and_counters, config=config),
                            optimizer, has_counters=True)
