"""Attention for the decoders: one dispatcher with two entry points, and the
splash kernel.

:func:`causal_attention` is what a model's block calls, with its
``attn_impl`` string; :func:`block_diffusion_attention` is the same for a
block-diffusion row (``models/block_diffusion.py``: 2S positions, the noised
and the clean copy, under the three-part block mask).  The dispatcher decides
the implementation, and everything that follows from the choice lives here
with it:

- ``"splash"`` is the fused flash kernel below; ``"auto"`` is splash on the
  TPU and the einsum elsewhere (the CPU tests' reference).  A kernel the
  compiler refuses is an error, never a quiet switch to a slower path.
- ``"xla"`` is einsum, float32 softmax, einsum.  Its (B, H, S, S) score
  tensor is pure HBM traffic (805 MB a layer for GPT-2 124M at S=1024), which
  is what the kernel exists to avoid.
- ``"ring"`` / ``"ulysses"`` are the context-parallel paths
  (``ops/ring_attention.py``): attention runs seq-sharded over the ambient
  mesh's `seq` axis (``jit_train_step(mesh=)`` installs the mesh).  Causal
  only: the block mask has no context-parallel path.

q is (B, S, H, head_dim) and k, v are (B, S, KV, head_dim) with H a multiple
of KV: grouped-query attention arrives at its own head count (KV == H is plain
MHA).  The splash kernel takes K and V so: it reads the K/V head ``h // (H //
KV)`` for query head ``h`` and sums dk and dv over the group in VMEM, so
nothing copies K and V out to H heads in HBM, forward or backward (0.55 GB a
layer for Mistral-7B at 8192 tokens, PERF.md PR 27).  The other three paths
want equal head counts, and the dispatcher repeats K and V for them, deciding
from the implementation it is about to call and the shapes it holds.

Splash keeps scores in VMEM tiles, never materializes them, and skips the
blocks the mask empties (the causal half; of a block-diffusion row's 2S x 2S
the noised copy's off-diagonal blocks, the clean copy's upper half and all a
clean query would read of the noised copy).  A block the mask cuts through
computes its part of the mask from the positions, in the kernel: no S x S
array exists.  head_dim=64 compiles unpadded under the
512x512 blocks on the v5e and agrees with the einsum (chip_smoke.py, kernel
phase).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

ATTN_IMPLS = ("auto", "xla", "splash", "ring", "ulysses")

#: The name the kernel's forward gives (``jax.ad_checkpoint.checkpoint_name``)
#: to the two arrays its backward kernels read besides q, k and v: the
#: attention output and its log-sum-exp, (B, H, S, head_dim) in the compute
#: dtype and (B, H, S) float32.
SPLASH_RESIDUALS = "splash_residuals"

#: Remat policy for a ``jax.checkpoint`` around a block that calls
#: :func:`splash_attention`: keep those two arrays and nothing else.  Splash is
#: a ``custom_vjp``; under a bare checkpoint nothing tells remat that its
#: backward wants them, so the backward runs the forward kernel a second time
#: only to get them back.  With the XLA path (or any block without the kernel)
#: there is no such name in the jaxpr and the policy saves nothing.
#:
#: It keys on the name and not on the ``pallas_call`` equation: the call's own
#: log-sum-exp output is padded to 128 lanes, (B, H, S, 128) float32, twice the
#: bytes of the attention output, where the named value is the one lane the
#: backward reads.  It also leaves every other kernel alone (a ring step that
#: kept each partial output would multiply its memory by the ring's length).
save_splash_residuals = jax.checkpoint_policies.save_only_these_names(
    SPLASH_RESIDUALS)


def causal_attention(q, k, v, impl: str):
    """Causal attention by the implementation ``impl`` names (a model's
    ``attn_impl``; see the module docstring).  q: (B, S, H, head_dim); k, v:
    (B, S, KV, head_dim), H a multiple of KV.  -> (B, S, H, head_dim)."""
    return _attention(q, k, v, impl, 0)


def block_diffusion_attention(q, k, v, block_length: int, impl: str):
    """Attention over the noised and the clean copy of a row under
    :func:`block_diffusion_allowed`.  q: (B, 2S, H, head_dim); k, v:
    (B, 2S, KV, head_dim), the noised copy's S positions first.
    -> (B, 2S, H, head_dim)."""
    if block_length < 1 or (q.shape[1] // 2) % block_length:
        raise ValueError(
            f"block_diffusion_attention: block_length {block_length} must "
            f"divide the row's {q.shape[1] // 2} positions")
    return _attention(q, k, v, impl, block_length)


def _attention(q, k, v, impl: str, block_length: int):
    """The dispatcher.  ``block_length`` 0: causal over S positions; else the
    block-diffusion mask over 2S."""
    if impl not in ATTN_IMPLS:
        raise ValueError(
            f"Unknown attn_impl: {impl!r} (use {'|'.join(ATTN_IMPLS)})")
    if block_length and impl in ("ring", "ulysses"):
        raise ValueError(
            f"attn_impl {impl!r} is causal only: a block-diffusion row runs "
            "under auto|splash|xla")
    with jax.named_scope("attn_kernel"):
        if impl == "splash" or (impl == "auto"
                                and jax.default_backend() == "tpu"):
            return splash_attention(q, k, v, causal=True,
                                    block_length=block_length)
        if k.shape[2] != q.shape[2]:
            # Each K/V head serves a group of consecutive query heads.
            group = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
        if impl == "ring":
            from ray_tpu.ops.ring_attention import ring_attention

            return ring_attention(q, k, v, causal=True)
        if impl == "ulysses":
            from ray_tpu.ops.ring_attention import ulysses_attention

            return ulysses_attention(q, k, v, causal=True)
        scale = 1.0 / math.sqrt(q.shape[-1])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
            * scale
        S = q.shape[1]
        if block_length:
            at = np.arange(S)
            mask = block_diffusion_allowed(at[:, None], at[None, :], S // 2,
                                           block_length)
        else:
            mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _splash_kernel(seq_len: int, n_heads: int, block_q: int, block_kv: int,
                   causal: bool, block_length: int = 0):
    # NOT cached: the kernel object built during one jit trace captures that
    # trace's context — reusing it from a later trace raises
    # UnexpectedTracerError.  Construction is cheap (lazy mask, no arrays).
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    if block_length:
        mask = _block_diffusion_mask(sm, seq_len, block_length)
    else:
        mask = (sm.CausalMask if causal else sm.FullMask)((seq_len, seq_len))
    mask = sm.MultiHeadMask([mask] * n_heads)
    interpret = jax.default_backend() != "tpu"
    bq = min(block_q, seq_len)
    bkv = min(block_kv, seq_len)
    bs = sk.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
        use_fused_bwd_kernel=True,
    )
    return sk.make_splash_mha(mask, head_shards=1, q_seq_shards=1,
                              block_sizes=bs, interpret=interpret,
                              residual_checkpoint_name=SPLASH_RESIDUALS)


def block_diffusion_allowed(i, j, seq_len: int, block_length: int):
    """May query position ``i`` read key position ``j``?  Both index the 2S
    positions ``[noised ; clean]`` of a row of ``seq_len`` ids in blocks of
    ``block_length`` (``models/block_diffusion.py`` has the why, and hands
    this on as ``allowed``): a noised query reads the noised keys of its own
    block and the clean keys of earlier blocks; a clean query reads the clean
    keys of its own and earlier blocks; nothing else.  numpy or jax integer
    arrays that broadcast: the splash kernel calls it on both."""
    def block_of(at):
        at = at - seq_len * (at >= seq_len)
        # The kernel computes this for every pair of a tile the mask cuts
        # through, and the chip's vector unit has no integer divide: ``//``
        # cost 18 of 890 ms a step in ``sdar-ep8-s8192`` (PERF.md, PR 34).
        # A power of two is a shift.
        if block_length & (block_length - 1) == 0:
            return at >> (block_length.bit_length() - 1)
        return at // block_length

    q_noised, k_noised = i < seq_len, j < seq_len
    bi, bj = block_of(i), block_of(j)
    return (q_noised & k_noised & (bi == bj)) \
        | (q_noised & ~k_noised & (bj < bi)) \
        | (~q_noised & ~k_noised & (bj <= bi))


def _block_diffusion_mask(sm, positions: int, block_length: int):
    """:func:`block_diffusion_allowed` over a row's 2S ``positions`` as a
    mask the splash kernel computes from the positions (its lazy masks'
    base class: the library has no public one; ``CausalMask`` is built the
    same way)."""
    class BlockDiffusionMask(sm._ComputableMask):
        # one instance serves every head; the library keeps distinct masks
        # apart by these
        def __eq__(self, other):
            return self is other

        def __hash__(self):
            return hash((type(self).__name__, positions, block_length))

    return BlockDiffusionMask(
        shape=(positions, positions),
        mask_function=lambda q_ids, kv_ids: block_diffusion_allowed(
            q_ids, kv_ids, positions // 2, block_length))


def splash_attention(q, k, v, causal: bool = True,
                     sm_scale: Optional[float] = None,
                     block_q: int = 512, block_kv: int = 512,
                     block_length: int = 0):
    """Production TPU attention (splash kernel): sparse over the causal
    mask when causal (no wasted upper-triangle work), full-mask
    bidirectional (ViT-style) otherwise, with a fused dq/dkv backward.  With
    a ``block_length`` the sequence is a block-diffusion row's 2S positions
    and the mask :func:`block_diffusion_allowed` (``causal`` is not read).

    q: (B, S, H, head_dim), k and v: (B, S, KV, head_dim), the model's native
    layout; H is a multiple of KV, and query head ``h`` attends to K/V head
    ``h // (H // KV)`` (``jnp.repeat``'s order).  KV == H is plain MHA.

    On more than one device the kernel must run inside a ``shard_map`` that
    makes every mesh axis manual: the SPMD partitioner cannot split a Mosaic
    custom call, and jax refuses to lower one it would have to ("Mosaic
    kernels cannot be automatically partitioned").  So under an ambient mesh
    (``jax.set_mesh``; ``jit_train_step(mesh=)`` installs it) the batch is
    divided over its `data` and `fsdp` axes and the heads over `tensor`;
    axes the spec does not name see replicated data.  `tensor` must divide
    KV as it must divide H, so that each chip holds whole groups.
    """
    _, S, H, hd = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)

    def local(q, k, v):
        kernel = _splash_kernel(S, q.shape[2], block_q, block_kv, causal,
                                block_length)
        # Splash takes (H, S, hd) per example; scale q up front (no scale arg).
        qt = (q * sm_scale).transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        return jax.vmap(kernel)(qt, kt, vt).transpose(0, 2, 1, 3)

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return local(q, k, v)
    KV, tensor = k.shape[2], mesh.shape.get("tensor", 1)
    if H % tensor or KV % tensor:
        raise ValueError(
            f"splash_attention: the mesh's tensor axis ({tensor}) must divide "
            f"the {KV} K/V heads as it must divide the {H} query heads: a "
            "chip's query heads read only the K/V heads it holds")
    batch_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
    spec = jax.sharding.PartitionSpec(
        batch_axes or None, None,
        "tensor" if "tensor" in mesh.axis_names else None, None)
    # check_vma off: the splash pallas_call does not declare vma on its
    # output avals, which the vma checker rejects.
    return jax.shard_map(local, in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)(q, k, v)

