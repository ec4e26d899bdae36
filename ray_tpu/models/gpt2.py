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
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # 50257 padded to a multiple of 128 for the MXU
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    seq_len: int = 1024
    dtype: Any = jnp.bfloat16
    remat: bool = True
    #: "save_attn" saves flash-attention outputs across the remat boundary —
    #: measured best on v5e (recomputing attention in bwd is the one thing
    #: worth HBM); "full" rematerializes everything.
    remat_policy: str = "save_attn"
    attn_impl: str = "auto"  # auto | xla | splash | ring | ulysses
    #: Pipeline stages over the mesh's `pipe` axis (parallel/pipeline.py);
    #: 1 = no pipelining. n_layer % pp_stages must be 0.
    pp_stages: int = 1
    #: GPipe microbatches; 0 = pp_stages (minimum). Must divide batch.
    pp_microbatches: int = 0
    #: Sequence-chunked LM-head loss: compute logits + cross-entropy in
    #: seq chunks of this size under jax.checkpoint, so the fp32 (B, S, V)
    #: logits tensor (3.3 GB for GPT-2-small at B=16) never hits HBM in
    #: either pass.  0 = single unchunked einsum.
    loss_chunk: int = 0
    #: LM-head loss implementation: "auto" flips to the fused pallas CE
    #: kernel (ops/fused_ce.py — logits never in HBM) when its roofline
    #: cost model predicts a win (small d_model / large-vocab regime;
    #: D=768 stays on the dense/chunked path), "fused"/"dense" force it.
    loss_impl: str = "auto"
    #: Dtype the (B, S, V) logits MATERIALIZE in.  bf16 halves the step's
    #: single biggest HBM tensor (fwd logits + bwd dlogits, ~1.6 GB each at
    #: B=16 fp32) for ~+1 MFU point on v5e; the loss reductions (logsumexp /
    #: target gather) still accumulate in fp32 so training is stable — only
    #: per-logit rounding changes (measured init-loss delta 0.01).  Set to
    #: jnp.float32 for exact-softmax parity.
    logits_dtype: Any = jnp.bfloat16
    #: lax.scan unroll factor over the stacked layers: >1 widens XLA's
    #: scheduling window so HBM-bound elementwise ops overlap matmuls
    #: across layer boundaries.
    scan_unroll: int = 1
    #: Splash-attention kernel tile sizes.
    attn_block_q: int = 512
    attn_block_kv: int = 512
    #: With remat_policy="attn_outside": also save the (B, S, 4D) MLP
    #: activation across the post-block checkpoint, skipping the mlp_in
    #: matmul's backward recompute for ~1.2 GB of activations (B=16).
    save_mlp_act: bool = False
    #: False = fully unroll the layer loop (a python loop, O(n_layer)
    #: compile depth) instead of lax.scan, for ANY remat policy (ignored
    #: when pp_stages > 1 — the pipeline schedule owns the layer loop).
    #: Removes the scan's dynamic-update-slice residual stacking
    #: (~10 ms/step in the r3 trace) at the cost of a longer first
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


def _attention(q, k, v, config: GPTConfig):
    """Causal attention.  q: (B, S, H, hd); k, v: (B, S, KV, hd) with H a
    multiple of KV (grouped-query attention; KV == H for GPT-2).

    "ring"/"ulysses" are the context-parallel paths (ops/ring_attention.py):
    attention runs seq-sharded over the mesh's `seq` axis — callers install
    the mesh via jax.set_mesh (parallel/train_state.py jit_train_step(mesh=)).
    """
    with jax.named_scope("attn_kernel"):
        return _attention_impl(q, k, v, config)


def _attention_impl(q, k, v, config: GPTConfig):
    impl = config.attn_impl
    if impl not in ("auto", "xla", "splash", "ring", "ulysses"):
        raise ValueError(
            f"Unknown attn_impl: {impl!r} "
            "(use auto|xla|splash|ring|ulysses)")
    # "auto" is the splash kernel on TPU and the XLA path elsewhere (the CPU
    # tests' reference).  A kernel the compiler refuses is an error, never a
    # quiet switch to a slower path.
    if impl == "splash" or (impl == "auto" and jax.default_backend() == "tpu"):
        from ray_tpu.ops.attention import splash_attention

        return splash_attention(q, k, v, causal=True,
                                block_q=config.attn_block_q,
                                block_kv=config.attn_block_kv)
    if k.shape[2] != q.shape[2]:
        # Every other path wants as many K/V heads as query heads: each K/V
        # head serves a group of consecutive query heads.
        group = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    if impl == "ring":
        from ray_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, causal=True)
    if impl == "ulysses":
        from ray_tpu.ops.ring_attention import ulysses_attention

        return ulysses_attention(q, k, v, causal=True)
    # XLA path: einsum softmax einsum; fp32 softmax.
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    S = q.shape[1]
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _block_pre_attn(x, blk, config: GPTConfig):
    """ln1 + qkv projection (the part BEFORE attention)."""
    from jax.ad_checkpoint import checkpoint_name

    dt = config.dtype
    with jax.named_scope("attn"):
        h = _layernorm(x, blk["ln1_scale"], blk["ln1_bias"]).astype(dt)
        h = checkpoint_name(h, "ln1_out")
        qkv = h @ blk["qkv_w"].astype(dt) + blk["qkv_b"].astype(dt)
        return checkpoint_name(qkv, "qkv")


def _block_post_attn(x, attn, blk, config: GPTConfig):
    """Residual out-projection + MLP (the part AFTER attention)."""
    from jax.ad_checkpoint import checkpoint_name

    dt = config.dtype
    with jax.named_scope("attn"):
        x = x + attn @ blk["out_w"].astype(dt) + blk["out_b"].astype(dt)
    with jax.named_scope("mlp"):
        h = _layernorm(x, blk["ln2_scale"], blk["ln2_bias"]).astype(dt)
        h = checkpoint_name(h, "ln2_out")
        h = jax.nn.gelu(h @ blk["mlp_in_w"].astype(dt)
                        + blk["mlp_in_b"].astype(dt))
        h = checkpoint_name(h, "mlp_act")
        return x + h @ blk["mlp_out_w"].astype(dt) + blk["mlp_out_b"].astype(dt)


def _block(x, blk, config: GPTConfig):
    """One transformer block (pre-attn half + attention + post-attn half);
    x: (B, S, D) in compute dtype."""
    from jax.ad_checkpoint import checkpoint_name

    B, S, D = x.shape
    H, hd = config.n_head, config.head_dim

    qkv = _block_pre_attn(x, blk, config)
    with jax.named_scope("attn"):
        q, k, v = jnp.split(qkv, 3, axis=-1)
        attn = _attention(q.reshape(B, S, H, hd), k.reshape(B, S, H, hd),
                          v.reshape(B, S, H, hd), config).reshape(B, S, D)
        attn = checkpoint_name(attn, "attn_out")
    return _block_post_attn(x, attn, blk, config)


def _final_norm(x, params, dt):
    with jax.named_scope("lm_head"):
        return _layernorm(x, params["lnf_scale"],
                          params["lnf_bias"]).astype(dt)


def forward_hidden(params: Dict[str, Any], tokens, config: GPTConfig):
    """tokens (B, S) int32 -> final-layernormed hidden states (B, S, D)."""
    B, S = tokens.shape
    dt = config.dtype
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(dt) + params["wpe"][:S].astype(dt)

    block_fn = partial(_block, config=config)
    if config.save_mlp_act and config.remat_policy != "attn_outside":
        raise ValueError(
            "save_mlp_act applies only to remat_policy='attn_outside' "
            "(use remat_policy='save_attn_mlp' with the scan path)")
    if config.remat and config.remat_policy == "attn_outside":
        # Attention OUTSIDE the remat regions: profiling (PERF.md r3 trace)
        # showed save_attn still re-ran the splash FORWARD in the backward
        # — saving the attention output does not save the kernel's own
        # custom-vjp residuals (lse), so the recompute regenerated them
        # (~10.8 ms/step).  Splitting the block into two checkpointed
        # halves with attention between them lets jax save q,k,v + lse
        # (~1.2 GB at B=16) and skip the re-forward entirely.  (The policy
        # that does save output + lse, on a whole block: models/llama.py.)
        # Only sound with flash-style attention kernels whose custom-vjp
        # residuals are VMEM-scale: the plain XLA path would instead save
        # the full (B, H, S, S) probs per layer for the backward (~5 GB
        # at the benchmark shape).  "auto" resolves to splash on TPU; on
        # CPU (tests) the shapes are tiny, so the XLA-path saves are fine.
        if config.attn_impl == "xla":
            raise ValueError(
                "remat_policy='attn_outside' with attn_impl='xla' would "
                "materialize per-layer (B, H, S, S) probs as saved "
                "residuals; use a flash-style attn_impl or save_attn")
        pre = jax.checkpoint(partial(_block_pre_attn, config=config))
        post_policy = (
            jax.checkpoint_policies.save_only_these_names("mlp_act")
            if config.save_mlp_act else None)
        post = (jax.checkpoint(partial(_block_post_attn, config=config),
                               policy=post_policy)
                if post_policy is not None
                else jax.checkpoint(partial(_block_post_attn, config=config)))
        H, hd = config.n_head, config.head_dim

        def split_body(carry, blk):
            x0 = carry
            qkv = pre(x0, blk)
            with jax.named_scope("attn"):
                q, k, v = jnp.split(qkv, 3, axis=-1)
                Bq, Sq = q.shape[0], q.shape[1]
                attn = _attention(
                    q.reshape(Bq, Sq, H, hd), k.reshape(Bq, Sq, H, hd),
                    v.reshape(Bq, Sq, H, hd), config).reshape(Bq, Sq, -1)
            return post(x0, attn, blk), None

        if config.pp_stages > 1:
            raise ValueError(
                "remat_policy='attn_outside' does not compose with "
                "pp_stages>1 yet; use save_attn")
        if config.scan_layers:
            x, _ = lax.scan(split_body, x, params["blocks"],
                            unroll=config.scan_unroll)
        else:
            for i in range(config.n_layer):
                blk_i = jax.tree_util.tree_map(lambda a: a[i],
                                               params["blocks"])
                x, _ = split_body(x, blk_i)
        return _final_norm(x, params, dt)
    if config.remat:
        policies = {
            "save_attn": lambda: jax.checkpoint_policies.save_only_these_names(
                "attn_out"),
            # Intermediate points on the recompute-vs-HBM curve: also save
            # the qkv projection and/or the mlp activation, skipping their
            # matmuls' recompute in the backward at ~0.9/1.2 GB of saved
            # activations (B=16).  Measured on v5e r3 — see PERF.md.
            "save_attn_qkv": lambda: jax.checkpoint_policies.save_only_these_names(
                "attn_out", "qkv"),
            "save_attn_mlp": lambda: jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mlp_act"),
            "save_attn_qkv_mlp": lambda: jax.checkpoint_policies.save_only_these_names(
                "attn_out", "qkv", "mlp_act"),
            # Save every matmul input/output across the boundary: bwd then
            # recomputes only elementwise ops (layernorm/gelu/adds).  ~3 GB
            # of saved activations at B=16 — the compiler-friendly stand-in
            # for remat=False (which crashes the TPU compiler helper).
            "save_matmuls": lambda: jax.checkpoint_policies.save_only_these_names(
                "ln1_out", "qkv", "attn_out", "ln2_out", "mlp_act"),
            "dots": lambda: jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            "everything": lambda: jax.checkpoint_policies.everything_saveable,
            "full": lambda: None,
        }
        if config.remat_policy not in policies:
            raise ValueError(
                f"unknown remat_policy {config.remat_policy!r} "
                f"(use {sorted(policies) + ['attn_outside']})")
        policy = policies[config.remat_policy]()
        block_fn = (jax.checkpoint(block_fn, policy=policy) if policy is not None
                    else jax.checkpoint(block_fn))

    def scan_body(carry, blk):
        return block_fn(carry, blk), None

    if not config.scan_layers and config.pp_stages == 1:
        # Unrolled layer loop for any remat policy (see scan_layers doc).
        for i in range(config.n_layer):
            blk_i = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            x, _ = scan_body(x, blk_i)
        return _final_norm(x, params, dt)

    if config.pp_stages > 1:
        # GPipe over the `pipe` mesh axis: each stage scans its local slice
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

        def stage_fn(local_blocks, h):
            h, _ = lax.scan(scan_body, h, local_blocks)
            return h

        x = pipeline_apply(
            stage_fn, params["blocks"], x,
            n_microbatches=config.pp_microbatches or config.pp_stages)
    else:
        x, _ = lax.scan(scan_body, x, params["blocks"],
                        unroll=config.scan_unroll)
    return _final_norm(x, params, dt)


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
        return _lm_head_loss(x, params["wte"], targets, config)


def _lm_head_loss(x, wte, targets, config: GPTConfig):
    """Tied LM head + cross-entropy on final hidden states (B, S, D)."""
    wte = wte.astype(config.dtype)
    B, S, D = x.shape
    C = config.loss_chunk
    impl = config.loss_impl
    if impl not in ("auto", "fused", "dense"):
        raise ValueError(f"loss_impl must be auto|fused|dense, got {impl!r}")
    if impl == "auto":
        # TPU-only flip (same gating as attn_impl): interpret-mode pallas
        # off-TPU would be a silent orders-of-magnitude slowdown.
        impl = "dense"
        if jax.default_backend() == "tpu":
            from ray_tpu._private.accelerators import device_peaks
            from ray_tpu.ops.fused_ce import fused_ce_wins

            if fused_ce_wins(D, jnp.dtype(config.logits_dtype).itemsize,
                             device_peaks(jax.devices()[0].device_kind)):
                impl = "fused"
    if impl == "fused":
        from ray_tpu.ops.fused_ce import fused_lm_head_ce

        return fused_lm_head_ce(x, wte, targets)
    if not C or C >= S:
        logits = jnp.einsum("bsd,vd->bsv", x, wte,
                            preferred_element_type=config.logits_dtype)
        # lse - target_logit (not log_softmax) keeps the (B,S,V) traffic
        # to one reduction pass — measured ~2 MFU points on v5e.  The
        # reductions upcast to fp32 regardless of the materialized dtype.
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        tgt_logit = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0].astype(jnp.float32)
        return jnp.mean(lse - tgt_logit)

    # Chunked head: per-chunk logits live only in VMEM-scale tiles; bwd
    # recomputes them under jax.checkpoint, so peak HBM holds (B, C, V)
    # instead of (B, S, V) in both passes.
    if S % C:
        raise ValueError(f"loss_chunk {C} must divide seq_len {S}")
    n = S // C
    xs = x.reshape(B, n, C, D).swapaxes(0, 1)      # (n, B, C, D)
    ts = targets.reshape(B, n, C).swapaxes(0, 1)   # (n, B, C)

    @jax.checkpoint
    def chunk_loss(x_c, t_c):
        logits = jnp.einsum("bsd,vd->bsv", x_c, wte,
                            preferred_element_type=config.logits_dtype)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(
            logits, t_c[..., None], axis=-1)[..., 0].astype(jnp.float32)
        return jnp.sum(lse - tgt)

    def body(acc, xt):
        return acc + chunk_loss(*xt), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts))
    return total / (B * S)


def make_optimizer(learning_rate=3e-4, weight_decay=0.1, b1=0.9, b2=0.95,
                   grad_clip=1.0, mu_dtype=None):
    """AdamW with the first moment stored in bf16 by default: the momentum
    is noise-tolerant (unlike nu, which stays fp32) and halving its HBM
    read+write is worth ~+0.8 MFU on v5e (r5 sweep: 47.5 -> 48.2; 13-step
    loss 9.562 vs 9.565).  Pass mu_dtype=jnp.float32 for exact parity."""
    import optax

    if mu_dtype is None:
        mu_dtype = jnp.bfloat16
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(learning_rate, b1=b1, b2=b2, weight_decay=weight_decay,
                    mu_dtype=mu_dtype),
    )


def make_train_step(config: GPTConfig, optimizer):
    """Pure (params, opt_state, tokens, targets) -> (params, opt_state, loss).

    Under jit with sharded inputs this is the whole distributed step: XLA
    derives the gradient psum/reduce-scatter from the shardings — there is no
    hand-written gradient sync (the DDP allreduce of the reference's
    _TorchBackend lives inside the compiled program here).
    """

    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets, config)
        import optax

        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def make_eval_step(config: GPTConfig):
    def step(params, tokens, targets):
        return loss_fn(params, tokens, targets, config)

    return step
