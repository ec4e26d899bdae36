"""GPT-2 — the flagship model, pure-JAX and mesh-native.

Counterpart of the reference's GPT-2 DDP train benchmark (BASELINE config 4;
ref harness python/ray/train/examples + release/train_tests), redesigned for
TPU: parameters are a plain pytree with *logical axis* annotations
(parallel/mesh.py) so one model definition runs under any dp/fsdp/tp/sp mesh;
blocks are stacked and scanned (`lax.scan`) for O(1) compile depth;
per-block rematerialization (`jax.checkpoint`) trades FLOPs for HBM; matmuls
run in bfloat16 on the MXU with fp32 layernorm/softmax/loss.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import causal_attention, save_splash_residuals
from ray_tpu.ops.lm_head import lm_head_cross_entropy
from ray_tpu.parallel.train_state import make_optimizer  # noqa: F401
from ray_tpu.parallel.train_state import make_train_step as _make_train_step

REMAT_POLICIES = ("block", "attn_outside")


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # 50257 padded to a multiple of 128 for the MXU
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    seq_len: int = 1024
    dtype: Any = jnp.bfloat16
    remat: bool = True
    #: What ``remat`` checkpoints.  "block": the whole block, keeping across
    #: the boundary only what the splash kernel's backward reads besides q, k
    #: and v (ops.attention.save_splash_residuals, the rule models/llama.py
    #: has).  "attn_outside": the two halves of the block around attention,
    #: each under its own checkpoint, so that q, k, v and the kernel's
    #: residuals (~1.2 GB at B=16 for the 124M model) are saved and the
    #: backward runs no attention forward; needs a flash-style attn_impl.
    remat_policy: str = "block"
    attn_impl: str = "auto"  # ops.attention.ATTN_IMPLS
    #: Pipeline stages over the mesh's `pipe` axis (parallel/pipeline.py);
    #: 1 = no pipelining. n_layer % pp_stages must be 0.
    pp_stages: int = 1
    #: GPipe microbatches; 0 = pp_stages (minimum). Must divide batch.
    pp_microbatches: int = 0
    #: Dtype the (B, S, V) logits MATERIALIZE in.  bf16 halves the step's
    #: single biggest HBM tensor (fwd logits + bwd dlogits, ~1.6 GB each at
    #: B=16 fp32) for ~+1 MFU point on v5e; the loss reductions (logsumexp /
    #: target gather) still accumulate in fp32 so training is stable — only
    #: per-logit rounding changes (measured init-loss delta 0.01).  Set to
    #: jnp.float32 for exact-softmax parity.
    logits_dtype: Any = jnp.bfloat16
    #: False = a Python loop over the layers (O(n_layer) compile depth) in
    #: place of lax.scan.  Removes the scan's dynamic-update-slice residual
    #: stacking (~10 ms/step in the r3 trace) at the cost of a longer first
    #: compile (~33 s vs ~15 s for GPT-2-small).
    scan_layers: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @staticmethod
    def small() -> "GPTConfig":
        return GPTConfig()  # 124M

    @staticmethod
    def tiny() -> "GPTConfig":
        return GPTConfig(vocab_size=1024, n_layer=2, n_head=4, d_model=128, seq_len=128)


def init_params(config: GPTConfig, key) -> Dict[str, Any]:
    """Plain pytree; blocks stacked on a leading layer axis for lax.scan."""
    k_wte, k_wpe, k_blocks = jax.random.split(key, 3)
    D, L, V, S = config.d_model, config.n_layer, config.vocab_size, config.seq_len
    std = 0.02
    resid_std = std / math.sqrt(2 * L)

    def norm(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s)

    ks = jax.random.split(k_blocks, 6)
    return {
        "wte": norm(k_wte, (V, D), std),
        "wpe": norm(k_wpe, (S, D), std / 2),
        "blocks": {
            "ln1_scale": jnp.ones((L, D)),
            "ln1_bias": jnp.zeros((L, D)),
            "qkv_w": norm(ks[0], (L, D, 3 * D), std),
            "qkv_b": jnp.zeros((L, 3 * D)),
            "out_w": norm(ks[1], (L, D, D), resid_std),
            "out_b": jnp.zeros((L, D)),
            "ln2_scale": jnp.ones((L, D)),
            "ln2_bias": jnp.zeros((L, D)),
            "mlp_in_w": norm(ks[2], (L, D, 4 * D), std),
            "mlp_in_b": jnp.zeros((L, 4 * D)),
            "mlp_out_w": norm(ks[3], (L, 4 * D, D), resid_std),
            "mlp_out_b": jnp.zeros((L, D)),
        },
        "lnf_scale": jnp.ones((D,)),
        "lnf_bias": jnp.zeros((D,)),
    }


def logical_axes(config: GPTConfig) -> Dict[str, Any]:
    """Logical-axis pytree matching init_params.  The leading stacked-layer
    axis is "layers": sharded over `pipe` when pipelining (each stage holds
    its contiguous slice of layers), unsharded otherwise (pipe=1)."""
    L = "layers"
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": {
            "ln1_scale": (L, "norm"),
            "ln1_bias": (L, "norm"),
            "qkv_w": (L, "embed", "heads"),
            "qkv_b": (L, "heads"),
            "out_w": (L, "heads", "embed"),
            "out_b": (L, "norm"),
            "ln2_scale": (L, "norm"),
            "ln2_bias": (L, "norm"),
            "mlp_in_w": (L, "embed", "mlp"),
            "mlp_in_b": (L, "mlp"),
            "mlp_out_w": (L, "mlp", "embed"),
            "mlp_out_b": (L, "norm"),
        },
        "lnf_scale": ("norm",),
        "lnf_bias": ("norm",),
    }


def num_params(config: GPTConfig) -> int:
    D, L, V, S = config.d_model, config.n_layer, config.vocab_size, config.seq_len
    per_block = 4 * D + 3 * D * D + 3 * D + D * D + D + 8 * D * D + 4 * D + D
    return V * D + S * D + L * per_block + 2 * D


def flops_per_token(config: GPTConfig) -> float:
    """6*P (fwd+bwd matmul) + attention score/value FLOPs (PaLM appendix B)."""
    return 6.0 * num_params(config) + 12.0 * config.n_layer * config.d_model * config.seq_len


def _layernorm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * lax.rsqrt(var + eps) * scale + bias
    return out


def _block_pre_attn(x, blk, config: GPTConfig):
    """ln1 + qkv projection (the part BEFORE attention)."""
    dt = config.dtype
    with jax.named_scope("attn"):
        h = _layernorm(x, blk["ln1_scale"], blk["ln1_bias"]).astype(dt)
        return h @ blk["qkv_w"].astype(dt) + blk["qkv_b"].astype(dt)


def _block_post_attn(x, attn, blk, config: GPTConfig):
    """Residual out-projection + MLP (the part AFTER attention)."""
    dt = config.dtype
    with jax.named_scope("attn"):
        x = x + attn @ blk["out_w"].astype(dt) + blk["out_b"].astype(dt)
    with jax.named_scope("mlp"):
        h = _layernorm(x, blk["ln2_scale"], blk["ln2_bias"]).astype(dt)
        h = jax.nn.gelu(h @ blk["mlp_in_w"].astype(dt)
                        + blk["mlp_in_b"].astype(dt))
        return x + h @ blk["mlp_out_w"].astype(dt) + blk["mlp_out_b"].astype(dt)


def _block(x, blk, config: GPTConfig, half=lambda fn: fn):
    """One transformer block: the half before attention, attention, the half
    after; x: (B, S, D) in compute dtype.  ``half`` wraps each of the two
    halves (remat_policy="attn_outside" passes jax.checkpoint)."""
    B, S, D = x.shape
    H, hd = config.n_head, config.head_dim

    qkv = half(partial(_block_pre_attn, config=config))(x, blk)
    with jax.named_scope("attn"):
        q, k, v = jnp.split(qkv, 3, axis=-1)
        attn = causal_attention(
            q.reshape(B, S, H, hd), k.reshape(B, S, H, hd),
            v.reshape(B, S, H, hd), config.attn_impl).reshape(B, S, D)
    return half(partial(_block_post_attn, config=config))(x, attn, blk)


def _layer_body(config: GPTConfig):
    """``body(x, blk) -> x`` for one layer, under the config's remat."""
    if config.remat_policy not in REMAT_POLICIES:
        raise ValueError(
            f"unknown remat_policy {config.remat_policy!r} "
            f"(use {' or '.join(map(repr, REMAT_POLICIES))})")
    block = partial(_block, config=config)
    if not config.remat:
        return block
    if config.remat_policy == "block":
        return jax.checkpoint(block, policy=save_splash_residuals)
    # "attn_outside".  A checkpoint around the whole block does not know
    # that the splash kernel's custom-vjp backward wants the kernel's own
    # output and log-sum-exp, and re-runs the forward kernel to get them
    # unless a policy names them ("block" does).  With attention between two
    # checkpointed halves instead, jax saves q, k, v and those residuals and
    # the backward runs no attention forward at all.  Only sound with
    # flash-style kernels whose residuals are VMEM-scale: the einsum would
    # save the full (B, H, S, S) probs per layer for the backward (~5 GB at
    # the benchmark shape).  "auto" resolves to splash on TPU; on CPU
    # (tests) the shapes are tiny, so the einsum's saves are fine.
    if config.attn_impl == "xla":
        raise ValueError(
            "remat_policy='attn_outside' with attn_impl='xla' would "
            "materialize per-layer (B, H, S, S) probs as saved "
            "residuals; use a flash-style attn_impl or "
            "remat_policy='block'")
    if config.pp_stages > 1:
        raise ValueError(
            "remat_policy='attn_outside' does not compose with "
            "pp_stages>1 yet; use remat_policy='block'")
    return partial(block, half=jax.checkpoint)


def _layers(body, x, blocks, scan: bool):
    """``body`` over the stacked layers ``blocks`` (leading axis = layer):
    one lax.scan, or the Python loop."""
    if scan:
        return lax.scan(lambda x, blk: (body(x, blk), None), x, blocks)[0]
    for i in range(jax.tree_util.tree_leaves(blocks)[0].shape[0]):
        x = body(x, jax.tree_util.tree_map(lambda a: a[i], blocks))
    return x


def forward_hidden(params: Dict[str, Any], tokens, config: GPTConfig):
    """tokens (B, S) int32 -> final-layernormed hidden states (B, S, D)."""
    B, S = tokens.shape
    dt = config.dtype
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(dt) + params["wpe"][:S].astype(dt)

    layers = partial(_layers, _layer_body(config), scan=config.scan_layers)
    if config.pp_stages > 1:
        # GPipe over the `pipe` mesh axis: each stage runs its local slice
        # of the stacked blocks (leading "layers" axis is pipe-sharded).
        from ray_tpu.parallel.pipeline import pipeline_apply

        if config.n_layer % config.pp_stages:
            raise ValueError(
                f"n_layer {config.n_layer} % pp_stages {config.pp_stages} != 0")
        # The mesh is authoritative for the stage count: a mismatched config
        # would silently run a different schedule than requested.
        amesh = jax.sharding.get_abstract_mesh()
        if "pipe" in amesh.shape \
                and amesh.shape["pipe"] not in (1, config.pp_stages):
            raise ValueError(
                f"config.pp_stages={config.pp_stages} but mesh pipe axis is "
                f"{amesh.shape['pipe']}")
        x = pipeline_apply(
            lambda local_blocks, h: layers(h, local_blocks),
            params["blocks"], x,
            n_microbatches=config.pp_microbatches or config.pp_stages)
    else:
        x = layers(x, params["blocks"])
    with jax.named_scope("lm_head"):
        return _layernorm(x, params["lnf_scale"],
                          params["lnf_bias"]).astype(dt)


def forward(params: Dict[str, Any], tokens, config: GPTConfig):
    """tokens (B, S) int32 -> logits (B, S, V) fp32."""
    x = forward_hidden(params, tokens, config)
    # Tied LM head; logits accumulate in fp32 for a stable loss.
    with jax.named_scope("lm_head"):
        return jnp.einsum("bsd,vd->bsv", x,
                          params["wte"].astype(config.dtype),
                          preferred_element_type=jnp.float32)


def loss_fn(params, tokens, targets, config: GPTConfig):
    x = forward_hidden(params, tokens, config)
    with jax.named_scope("lm_head"):
        # Tied LM head.
        return lm_head_cross_entropy(x, params["wte"].astype(config.dtype),
                                     targets, config.logits_dtype)


def make_train_step(config: GPTConfig, optimizer):
    """Pure (params, opt_state, tokens, targets) -> (params, opt_state, loss):
    parallel.train_state.make_train_step over this model's loss."""
    return _make_train_step(partial(loss_fn, config=config), optimizer)
