"""Llama-family decoder: RMSNorm + RoPE + SwiGLU + grouped-query attention.

Second dense model family on the same parallel substrate as GPT-2 (the
reference is a runtime, not a model zoo — these models exist to prove the
framework's training path on the architectures users actually run).  The
module mirrors ``models/gpt2.py``'s functional contract exactly —
init_params / logical_axes / forward / loss_fn / make_train_step — so every
mesh axis (data/fsdp/tensor/seq via logical-axis rules, ring/ulysses
attention for long context) composes without model-specific glue.

A configuration may also carry the published facts of a mixture-of-experts
model of the same family (OLMoE-1B-7B: ``n_experts``, ``experts_per_token``,
``norm_topk_prob``, ``qk_norm``, the two router-loss coefficients).  The
block's attention half then normalises the whole q and k projections
(QK-norm), its second half is ``models/moe.py``'s dropless expert layer in
place of the SwiGLU MLP, the layer scan carries each layer's two router
losses out, and ``loss_fn`` adds them to the cross-entropy.  Without experts
none of this is traced: Mistral's program is what it was.

Further published facts, each defaulting to that program (SDAR-30B-A3B):
``head_dim`` apart from ``d_model / n_head``; ``qk_norm="head"``, an RMSNorm
over each head's ``head_dim`` with one vector shared by the heads;
``experts_held``, the run of expert ids this chip holds of every layer
(``models/moe.py``: the router stays ``n_experts`` wide, parameters exist for
the held experts only); and ``block_length`` / ``mask_token_id`` /
``noise_seed``, block-diffusion training (``models/block_diffusion.py``): the
layers see the noised and the clean copy of a row, 2S positions under
``ops.attention.block_diffusion_attention`` with RoPE positions that restart
at the clean copy, and :func:`loss_fn` is the weighted cross-entropy of the
noised copy's masked positions, the head run on those S positions only.

A looped stack (Ouro-2.6B; ``models/looped.py``), three more published
facts that default to the plain decoder: ``ut_steps``, how often a step runs
the stack of layers, with one set of weights; ``sandwich_norm``, a second
norm after each sub-layer (``attn_norm_2``, ``mlp_norm_2``); ``exit_beta``.
Above one pass :func:`forward_hidden` runs the layers' ``lax.scan`` inside
a ``lax.scan`` over the passes that closes over the one ``params["blocks"]``
(one layer body to compile whatever ``ut_steps`` is: 13 s for the v5e at
eight layers where the passes written out in a row took 15 and 0.4 GiB
more), the shared final norm ends every pass and feeds the next, and the
passes' normed states, (T, B, S, D), go to ``looped.loss_and_counters``:
the shared head a pass at a time, an exit gate, and the expectation of the
passes' cross-entropies over the exit pass.  What the loops stack: every
pass stacks its own layers' inputs and kernel outputs, so a step holds ``T x
L`` layer applications' (the outer scan stacks the inner scan's stacks), the
backward walks them last pass first, and a block's gradient is the sum of
its ``T`` passes' contributions, kept as a running sum beside the stacks the
pass at hand fills.  What the checkpoint keeps a pass is what it keeps a
layer below, ``T`` times: ``_layer_sizes`` sizes the ladder's rungs and the
bound by ``n_layer x ut_steps`` applications.  With experts or a
``block_length`` a loop is refused.

What the layer's ``jax.checkpoint`` keeps: the layer's input ``x`` (whole-block
remat) and, where the splash kernel runs, the kernel's attention output and
log-sum-exp (``ops.attention.save_splash_residuals``), one more (B, S, D)
activation a layer.  The kernel is a ``custom_vjp`` whose backward reads those
two arrays; under a bare checkpoint the backward ran the forward kernel a
second time to get them back (11.5 of 305 ms a step at 8192 tokens, PERF.md PR
24).  With the XLA attention path the policy finds nothing of the kernel's to
keep.

Where the chip has room, the layer keeps more (``ops/remat.py``, PR 31): q, k
and v as they enter the attention call, then the MLP's gate and up products
(with experts, the two grouped matmuls' outputs), each named with
``checkpoint_name`` and kept only if the bytes fit beside the step's own
temporaries and a reserve.  The choice is made in :func:`forward_hidden` when
the loss is traced, from the devices' ``memory_stats()`` and the shapes at
hand; no field of the configuration steers it, and a backend that reports no
memory (the CPU) gets the plain policy.  What the backward still re-does from
the kept arrays is elementwise: the two RMSNorms, the casts, ``silu(gate) *
up``, and with QK-norm the q and k products the norm's backward reads.

Under an `fsdp` mesh axis the seven dense projections' weight gradients are
not the partitioner's product-then-reduce-scatter: ``ops/grad_ring.py`` sums
each over the chips as a ring of chunk products whose partial sums travel by
``ppermute`` behind the next product (PR 32).  Which axis of a weight is
`embed` comes from :func:`logical_axes`; on one device the call is ``x @ w``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Union

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import block_diffusion, looped, moe
from ray_tpu.models.layers import attention, feed_forward, mesh_axes
from ray_tpu.models.layers import rmsnorm as _rmsnorm
from ray_tpu.models.layers import rope as _rope  # noqa: F401
from ray_tpu.ops import remat
from ray_tpu.ops.lm_head import lm_head_cross_entropy
from ray_tpu.parallel.train_state import make_optimizer  # noqa: F401
from ray_tpu.parallel.train_state import make_train_step as _make_train_step
from ray_tpu.util import first_call


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 8
    n_head: int = 8
    #: grouped-query attention: kv heads < query heads share k/v
    n_kv_head: int = 4
    d_model: int = 512
    #: width of one head; 0 means ``d_model // n_head``
    head_dim: int = 0
    #: SwiGLU hidden dim (Llama uses ~8/3 * d_model rounded to 256)
    d_ff: int = 1408
    seq_len: int = 1024
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    attn_impl: str = "auto"  # ops.attention.ATTN_IMPLS
    logits_dtype: Any = jnp.bfloat16
    # Published facts of a mixture-of-experts model (OLMoE-1B-7B); Mistral
    # has none of them.  With experts the block's second half is
    # models/moe.py's dropless layer over n_experts SwiGLU experts of width
    # d_ff, experts_per_token of them a token.
    n_experts: int = 0
    experts_per_token: int = 0
    #: renormalise the chosen experts' probabilities to sum to one
    norm_topk_prob: bool = False
    #: True: RMSNorm over the whole q and the whole k projection, before
    #: the heads are split (OLMoE).  "head": over each head's head_dim, one
    #: vector shared by the heads (SDAR).  Either way before RoPE.
    qk_norm: Union[bool, str] = False
    #: loss = CE + router_aux_loss_coef x load-balance + router_z_loss_coef x
    #: z, each summed over the layers (moe.router_losses)
    router_aux_loss_coef: float = 0.0
    router_z_loss_coef: float = 0.0
    #: the run of expert ids this chip holds of every layer, a ``range``
    #: (models/moe.py); None means all ``n_experts``: read :attr:`held`
    experts_held: Optional[range] = None
    # Block-diffusion training (models/block_diffusion.py); 0 is causal
    # next-token training.
    block_length: int = 0
    #: the id a noised position shows; inside the vocabulary, outside the data
    mask_token_id: int = 0
    #: the noise is a function of a row's ids and this
    noise_seed: int = 0
    # Published facts of a looped model (Ouro-2.6B; models/looped.py), each
    # defaulting to the plain decoder.
    #: how often a step runs the stack of layers, with one set of weights;
    #: above 1 every pass ends in the final norm, the head and an exit gate
    ut_steps: int = 1
    #: a second norm after each sub-layer: ``x + norm_2(f(norm(x)))``
    sandwich_norm: bool = False
    #: the coefficient of the exit distribution's entropy in the loss
    exit_beta: float = 0.0

    @property
    def held(self) -> range:
        """The expert ids whose matrices exist here."""
        return range(self.n_experts) if self.experts_held is None \
            else self.experts_held

    @property
    def q_per_kv(self) -> int:
        return self.n_head // self.n_kv_head

    @staticmethod
    def tiny() -> "LlamaConfig":
        return LlamaConfig(vocab_size=1024, n_layer=2, n_head=4, n_kv_head=2,
                           d_model=128, d_ff=384, seq_len=128)

    @staticmethod
    def tiny_moe() -> "LlamaConfig":
        """OLMoE's shape in small: MHA, QK-norm, 8 experts of 64, 2 a token."""
        return LlamaConfig(vocab_size=1024, n_layer=2, n_head=4, n_kv_head=4,
                           d_model=128, d_ff=64, seq_len=128, n_experts=8,
                           experts_per_token=2, qk_norm=True,
                           router_aux_loss_coef=0.01,
                           router_z_loss_coef=0.001)

    @staticmethod
    def tiny_ouro() -> "LlamaConfig":
        """Ouro's shape in small: MHA, sandwich norms, four passes."""
        return LlamaConfig(vocab_size=1024, n_layer=2, n_head=4, n_kv_head=4,
                           d_model=128, d_ff=384, seq_len=128, rms_eps=1e-6,
                           ut_steps=4, sandwich_norm=True, exit_beta=0.1)

    @staticmethod
    def tiny_sdar() -> "LlamaConfig":
        """SDAR's shape in small: heads of 64 at d_model / n_head = 32, GQA,
        per-head QK-norm, experts 2 and 3 of 8 held, blocks of 4."""
        return LlamaConfig(vocab_size=1024, n_layer=2, n_head=4, n_kv_head=2,
                           d_model=128, head_dim=64, d_ff=64, seq_len=128,
                           n_experts=8, experts_per_token=2,
                           norm_topk_prob=True, qk_norm="head",
                           experts_held=range(2, 4),
                           router_aux_loss_coef=0.001, block_length=4,
                           mask_token_id=1023)

    def __post_init__(self):
        if not self.head_dim:
            assert self.d_model % self.n_head == 0
            object.__setattr__(self, "head_dim", self.d_model // self.n_head)
        held = self.held
        assert held.step == 1 and 0 <= held.start <= held.stop \
            <= self.n_experts and bool(held) == bool(self.n_experts), held
        assert self.qk_norm in (False, True, "head"), self.qk_norm
        assert self.n_head % self.n_kv_head == 0
        assert 0 <= self.experts_per_token <= self.n_experts
        assert (self.n_experts > 0) == (self.experts_per_token > 0)
        assert not self.block_length \
            or 0 <= self.mask_token_id < self.vocab_size
        if self.ut_steps < 1 or self.ut_steps > 1 and (
                self.n_experts or self.block_length):
            raise ValueError(
                f"ut_steps={self.ut_steps} with n_experts={self.n_experts}, "
                f"block_length={self.block_length}: the loop over passes "
                "runs a dense next-token decoder, at least once")


def init_params(config: LlamaConfig, key) -> Dict[str, Any]:
    """Plain pytree; blocks stacked on a leading layer axis for lax.scan."""
    D, L, V = config.d_model, config.n_layer, config.vocab_size
    H, KV, hd, F = config.n_head, config.n_kv_head, config.head_dim, config.d_ff
    std = 0.02
    # 2 L T residual branches: every pass adds a layer's two
    resid_std = std / math.sqrt(2 * L * config.ut_steps)
    k_wte, k_blocks, k_head = jax.random.split(key, 3)

    def norm(key, shape, s):
        return jax.random.normal(key, shape, jnp.float32) * s

    ks = jax.random.split(k_blocks, 8)
    # With experts the three MLP matrices gain a leading axis over the
    # experts held here.
    E = (len(config.held),) if config.n_experts else ()
    blocks = {
        "attn_norm": jnp.ones((L, D)),
        "wq": norm(ks[0], (L, D, H * hd), std),
        "wk": norm(ks[1], (L, D, KV * hd), std),
        "wv": norm(ks[2], (L, D, KV * hd), std),
        "wo": norm(ks[3], (L, H * hd, D), resid_std),
        "mlp_norm": jnp.ones((L, D)),
        "w_gate": norm(ks[4], (L, *E, D, F), std),
        "w_up": norm(ks[5], (L, *E, D, F), std),
        "w_down": norm(ks[6], (L, *E, F, D), resid_std),
    }
    if config.n_experts:
        blocks["router"] = norm(ks[7], (L, D, config.n_experts), std)
    if config.qk_norm == "head":
        blocks["q_norm"] = jnp.ones((L, hd))
        blocks["k_norm"] = jnp.ones((L, hd))
    elif config.qk_norm:
        blocks["q_norm"] = jnp.ones((L, H * hd))
        blocks["k_norm"] = jnp.ones((L, KV * hd))
    if config.sandwich_norm:
        blocks["attn_norm_2"] = jnp.ones((L, D))
        blocks["mlp_norm_2"] = jnp.ones((L, D))
    params = {
        "wte": norm(k_wte, (V, D), std),
        "blocks": blocks,
        "final_norm": jnp.ones((D,)),
        # Untied LM head (Llama convention; GPT-2 ties to wte).
        "lm_head": norm(k_head, (V, D), std),
    }
    if config.ut_steps > 1:
        params["exit_gate"] = looped.init_gate(D)
    return params


def logical_axes(config: LlamaConfig) -> Dict[str, Any]:
    L = "layers"
    E = ("expert",) if config.n_experts else ()
    blocks = {
        "attn_norm": (L, "norm"),
        "wq": (L, "embed", "heads"),
        "wk": (L, "embed", "heads"),
        "wv": (L, "embed", "heads"),
        "wo": (L, "heads", "embed"),
        "mlp_norm": (L, "norm"),
        "w_gate": (L, *E, "embed", "mlp"),
        "w_up": (L, *E, "embed", "mlp"),
        "w_down": (L, *E, "mlp", "embed"),
    }
    if config.n_experts:
        blocks["router"] = (L, "embed", None)
    if config.qk_norm:
        blocks["q_norm"] = (L, "norm")
        blocks["k_norm"] = (L, "norm")
    if config.sandwich_norm:
        blocks["attn_norm_2"] = (L, "norm")
        blocks["mlp_norm_2"] = (L, "norm")
    axes = {
        "wte": ("vocab", "embed"),
        "blocks": blocks,
        "final_norm": ("norm",),
        "lm_head": ("vocab", "embed"),
    }
    if config.ut_steps > 1:
        axes["exit_gate"] = looped.GATE_AXES
    return axes


def _attn_params(config: LlamaConfig) -> int:
    hd = config.head_dim
    return config.d_model * hd * 2 * (config.n_head + config.n_kv_head)


def num_params(config: LlamaConfig) -> int:
    """Parameters that exist here: of the experts, the held ones."""
    D, L, V, F = (config.d_model, config.n_layer, config.vocab_size,
                  config.d_ff)
    attn = _attn_params(config)
    mlp = 3 * D * F
    if config.n_experts:
        mlp = len(config.held) * mlp + D * config.n_experts
    if config.qk_norm == "head":
        attn += 2 * config.head_dim
    elif config.qk_norm:
        attn += (config.n_head + config.n_kv_head) * config.head_dim
    per_block = (4 if config.sandwich_norm else 2) * D + attn + mlp
    return 2 * V * D + L * per_block + D + _gate_params(config)


def _gate_params(config: LlamaConfig) -> int:
    """The exit gate's weight and bias, where the stack is looped."""
    return config.d_model + 1 if config.ut_steps > 1 else 0


def flops_per_token(config: LlamaConfig) -> float:
    """Per trained token: 6 x the parameters a position meets (of the held
    experts its own, in expectation under an even router) plus attention,
    12 x width x S a layer (the whole square, as ever).  A block-diffusion
    row sends two positions a trained token through the layers and one
    through the head, and its attention is counted over the mask's own area,
    S^2 + S x block_length a head.  A looped stack meets its layers, the
    final norm, the head and the gate ``ut_steps`` times a token, the
    embedding once."""
    L, S, T = config.n_layer, config.seq_len, config.ut_steps
    held = len(config.held)
    idle = held * (1 - config.experts_per_token / max(config.n_experts, 1)) \
        * 3 * config.d_model * config.d_ff * L
    every_pass = (config.vocab_size + 1) * config.d_model \
        + _gate_params(config)
    outside = config.vocab_size * config.d_model + every_pass
    layers = num_params(config) - outside - idle
    copies, area = (2, S + config.block_length) if config.block_length \
        else (1, S)
    return 6.0 * (T * copies * layers + outside + (T - 1) * every_pass) \
        + 12.0 * T * L * config.n_head * config.head_dim * area


def _block(x, blk, config: LlamaConfig):
    """One layer: ``models/layers.py``'s two halves.  -> (x, what the expert
    layer says of itself): the second is ``None`` for a dense MLP; with
    experts it is (moe.router_losses' pair, the layer's counts:
    ``moe.moe_mlp``)."""
    axes = logical_axes(config)["blocks"]
    return feed_forward(attention(x, blk, config, axes), blk, config, axes)


def _layer_policy(params, x_shape, config: LlamaConfig):
    """The layer's checkpoint policy: ``ops.remat.layer_policy`` over this
    model's sizes a chip."""
    return remat.layer_policy(*_layer_sizes(params, x_shape, config))


def _layer_sizes(params, x_shape, config: LlamaConfig):
    """What ``ops.remat`` needs to know of ``params`` (arrays or their
    shapes) and activations of ``x_shape`` (B, S, D), every size a chip's:
    (the ladder's candidates as (name, bytes), with experts the routing's
    behind them, which is kept whatever the rule answers; the bound on the
    step's own temporaries).  The ambient mesh cuts the tokens over its
    batch and `seq` axes and the heads and the MLP's width over `tensor`.
    The parameters are taken to lie as ``logical_axes`` and the default
    rules put them: a trace sees no shardings, and a caller who hands
    ``create_sharded_state`` rules of their own gets the chip's share
    mis-sized, which the compiler's refusal and ``TrainStep``'s fallback
    then have to catch.  The scan stacks whatever is kept, so a candidate's
    bytes are a layer's times ``n_layer``, and a looped stack's
    (``ut_steps``) that many passes' besides: every pass stacks its own."""
    mesh = jax.sharding.get_abstract_mesh()
    tensor = remat.axis_shards(mesh, "tensor")
    tokens = math.prod(x_shape[:2]) // remat.axis_shards(
        mesh, "data", "fsdp", "seq")
    item = jnp.dtype(config.dtype).itemsize
    qkv_width = (config.n_head + 2 * config.n_kv_head) * config.head_dim \
        // tensor
    # a token meets experts_per_token experts of width d_ff
    mlp_width = config.d_ff * max(config.experts_per_token, 1) // tensor
    whole = jax.tree.map(lambda a: 4 * a.size, params)
    chips = jax.tree.map(
        lambda nbytes, axes: nbytes // remat.axis_shards(
            mesh, *mesh_axes(axes)),
        whole, logical_axes(config))
    total, block_bytes = (sum(jax.tree.leaves(t)) for t in
                          (chips, chips["blocks"]))
    temporaries = remat.own_temporaries(
        block_bytes=block_bytes, other_bytes=total - block_bytes,
        layer_bytes=sum(jax.tree.leaves(whole["blocks"])) // config.n_layer,
        sharded=total < sum(jax.tree.leaves(whole)), tokens=tokens,
        d_model=config.d_model, n_layer=config.n_layer,
        attn_width=config.n_head * config.head_dim // tensor,
        n_head=config.n_head // tensor,
        mlp_width=mlp_width, vocab=config.vocab_size // tensor,
        itemsize=item,
        logits_itemsize=jnp.dtype(config.logits_dtype).itemsize,
        passes=config.ut_steps)
    if config.n_experts:
        # what the bound does not know of: the expert layer moves every
        # (position, expert) pair as a row of d_model (models/moe.py): the
        # rows in expert order, the down-projection's output, and the
        # cotangent of each
        temporaries += 4 * tokens * config.experts_per_token \
            * config.d_model * item
    per_layer = {remat.QKV: tokens * qkv_width * item,
                 remat.GATE_UP: 2 * tokens * mlp_width * item}
    names = remat.LADDER
    if config.n_experts:  # kept whatever the rule answers
        per_layer[remat.ROUTING] = moe.routing_bytes(
            tokens, config.n_experts, config.experts_per_token)
        names += (remat.ROUTING,)
    applications = config.n_layer * config.ut_steps
    return ([(name, applications * per_layer[name]) for name in names],
            temporaries)


def forward_hidden(params: Dict[str, Any], tokens, config: LlamaConfig):
    """-> (final hidden states (B, S, D), router loss, expert counts): the
    second is the coefficient-weighted sum of the layers' router losses, the
    third ``moe.moe_mlp``'s counts with the layers in front: ``moe_rows``
    (L, shards, H) int32 and, where the layers hold a share, ``moe_moved``
    (L, shards); both ``None`` (no scan output behind them) for a model
    without experts.  A looped stack (``ut_steps`` > 1) hands back the
    normed state after every pass, (T, B, S, D), the last pass last."""
    dt = config.dtype
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(dt)

    def layer(x, blk):
        return _block(x, blk, config)

    if config.remat:
        layer = jax.checkpoint(
            layer, policy=_layer_policy(params, x.shape, config))

    def final_norm(x):
        with jax.named_scope("lm_head"):
            return _rmsnorm(x, params["final_norm"],
                            config.rms_eps).astype(dt)

    if config.ut_steps > 1:
        # The passes are an outer scan with the blocks closed over: one
        # layer body to compile whatever ``ut_steps`` is, and the next pass
        # reads the normed state.
        def one_pass(x, _):
            x = final_norm(lax.scan(layer, x, params["blocks"])[0])
            return x, x

        return lax.scan(one_pass, x, None, length=config.ut_steps)[1], \
            None, None
    x, expert_layers = lax.scan(layer, x, params["blocks"])
    router_loss = counts = None
    if expert_layers is not None:
        (balance, z), counts = expert_layers  # (L,) each; moe_mlp's
        router_loss = config.router_aux_loss_coef * jnp.sum(balance) \
            + config.router_z_loss_coef * jnp.sum(z)
    return final_norm(x), router_loss, counts


def forward(params: Dict[str, Any], tokens, config: LlamaConfig):
    x = forward_hidden(params, tokens, config)[0]
    if config.ut_steps > 1:  # the last pass's logits
        x = x[-1]
    with jax.named_scope("lm_head"):
        return jnp.einsum("bsd,vd->bsv", x,
                          params["lm_head"].astype(config.dtype),
                          preferred_element_type=jnp.float32)


def loss_fn(params, tokens, targets, config: LlamaConfig):
    """Mean next-token cross-entropy of ``tokens`` against ``targets``, plus
    the router losses.  With a ``block_length``: the block-diffusion loss of
    the rows ``tokens`` (``models/block_diffusion.py``), a masked position
    predicting the id it covers; ``targets`` is then not read."""
    return loss_and_counters(params, tokens, targets, config)[0]


def loss_and_counters(params, tokens, targets, config: LlamaConfig):
    """-> (:func:`loss_fn`'s scalar, the step counters of
    ``tracing.STEP_COUNTER_REGISTRY`` this model has: with experts
    ``moe_rows``, with a share of them held ``moe_moved`` too; a looped
    stack's ``loss_ut`` and ``ut_exit_mass``, its loss being
    ``models/looped.py``'s expectation over the exit pass; else none)."""
    weights = None
    if config.block_length:
        targets = tokens
        tokens, weights = block_diffusion.noise(
            tokens, config.noise_seed, config.block_length,
            config.mask_token_id)
    first_call.note(experts_held=len(config.held),
                    experts_total=config.n_experts,
                    block_length=config.block_length,
                    attn_positions=tokens.shape[1],
                    loss_positions=targets.shape[1],
                    ut_steps=config.ut_steps)
    x, router_loss, counts = forward_hidden(params, tokens, config)
    if config.ut_steps > 1:
        return looped.loss_and_counters(x, params, targets, config)
    with jax.named_scope("lm_head"):
        if config.block_length:  # the head reads the noised copy, the first
            x = x[:, :targets.shape[1]]
        ce = lm_head_cross_entropy(x, params["lm_head"].astype(config.dtype),
                                   targets, config.logits_dtype, weights)
    if router_loss is None:
        return ce, {}
    return ce + router_loss, counts


def make_train_step(config: LlamaConfig, optimizer):
    """Pure (params, opt_state, tokens, targets) -> (params, opt_state, loss):
    parallel.train_state.make_train_step over this model's loss.  With
    experts the step also leaves ``moe_rows`` (and, a share of them held,
    ``moe_moved``) in ``step.counters``, a looped stack's ``loss_ut`` and
    ``ut_exit_mass``; a dense model's step is the plain one."""
    if config.n_experts or config.ut_steps > 1:
        return _make_train_step(partial(loss_and_counters, config=config),
                                optimizer, has_counters=True)
    return _make_train_step(partial(loss_fn, config=config), optimizer)
