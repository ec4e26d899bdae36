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
MHA).  v may come at a head dimension of its own (a latent-attention layer's
q.k head is 192 wide and its v head 128, ``models/mla.py``): the output is
then as wide as v, and the scale and the block rule read q's.  The splash
kernel and the einsum take it so; the context-parallel paths do not.  The
splash kernel takes K and V so: it reads the K/V head ``h // (H //
KV)`` for query head ``h`` and sums dk and dv over the group in VMEM, so
nothing copies K and V out to H heads in HBM, forward or backward (0.55 GB a
layer for Mistral-7B at 8192 tokens, PERF.md PR 27).  The other three paths
want equal head counts, and the dispatcher repeats K and V for them, deciding
from the implementation it is about to call and the shapes it holds.

Splash keeps scores in VMEM tiles, never materializes them, and skips the
blocks the mask empties (the causal half; of a block-diffusion row's 2S x 2S
the noised copy's off-diagonal blocks, the clean copy's upper half and all a
clean query would read of the noised copy).  A causal block computes its part
of the mask from the positions, in the kernel (one compare).  The
block-diffusion rule is too dear for that (some thirty integer operations a
pair, on a kernel its vector unit already bounds), so its mask goes to the
library as a lazy object that is read a block at a time in numpy while the
step is traced: whole blocks are told apart there and pay one ``or`` in the
kernel, and a block the mask cuts through reads one of three stored tiles.
No 2S x 2S array exists.

The blocks a call runs in follow the row it runs on: :func:`splash_blocks`
picks them where the kernel is built, from the row's kv length and the q.k
head dimension (1024 x 1024 at head 128 or 192 on a row of 2048 or more, 512
x 512 otherwise, from sweeps on the v5e), the forward's and the fused
backward's apart.  What the call costs a
head (``attn_calls``, ``attn_blocks``, ``attn_blocks_cut``,
``attn_grid_steps_fwd``, ``attn_grid_steps_bwd``) and in what blocks
(``attn_block_q``, ``attn_block_kv``, ``attn_block_q_bwd``,
``attn_block_kv_bwd``, ``attn_dq_partials``) is on the ``train.first_call``
span and record.  head_dim=64 compiles unpadded under the 512x512 blocks on
the v5e and agrees with the einsum (chip_smoke.py, kernel phase).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.placement import place
from ray_tpu.util import first_call

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
    (B, S, KV, head_dim), H a multiple of KV, v's head dimension its own
    where it differs.  -> (B, S, H, v's head_dim)."""
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


class SplashBlocks(NamedTuple):
    """The block sizes of one splash call.  The library takes the forward's
    and the fused backward's apart, and their costs differ: only the
    backward writes a dq partial for every kv block of the row, and only its
    grid is walked whole, steps without work included."""

    q: int
    kv: int
    kv_compute: int
    q_bwd: int
    kv_bwd: int
    kv_bwd_compute: int

    @classmethod
    def square(cls, block: int) -> "SplashBlocks":
        return cls(*(block,) * 6)

    def capped(self, seq_len: int) -> "SplashBlocks":
        """No block longer than the row, no compute sub-block longer than
        its block."""
        q, kv, kvc, qb, kvb, kvbc = (min(b, seq_len) for b in self)
        return SplashBlocks(q, kv, min(kvc, kv), qb, kvb, min(kvbc, kvb))


def splash_blocks(kv_len: int, head_dim: int) -> SplashBlocks:
    """The blocks a splash call runs in, from what the call site holds: the
    row's kv length and the q.k head dimension.  Swept on the v5e at the seven
    shapes the benchmark's cells run, forward and forward + fused backward +
    the sum of its dq partials, then held against the cells' traces (PERF.md,
    PR 42; ``scripts/splash_block_sweep.py``):

    - head 128 on a row of 2048 or more: q and kv blocks of 1024, the compute
      sub-block 512, forward and backward alike, whatever the mask (causal or
      block diffusion) and the row (4096 to 16384 measured).  Against blocks
      of 512 the forward is 6-27 % faster and forward + backward 8-15 %: a
      quarter of the grid steps, K and V read half as often, half the dq
      partials written and summed, and on a block-diffusion row a quarter of
      the backward's steps without work.  Longer blocks gain nothing more (a
      kv block of 2048 halves the partials again and computes as much more
      under the mask) or exceed the scoped VMEM (a q block of 2048 with most
      kv blocks; kv blocks of 2048 with a compute sub-block that long).
    - a q.k head of 192 over a v head of 128 (latent attention; a row of
      8192, 32 heads, swept at PR 47): the same blocks.  Against 512 the
      forward is 12 % faster (7.08 against 8.02 ms) and forward + backward
      9 % (25.3 against 27.9): a quarter of the grid steps and half the dq
      partials, as at 128.  A compute sub-block of 1024 reads the same
      within 1 %; q or kv blocks of 2048 are refused for scoped VMEM with
      most partners, sooner than at 128 (the tiles are half again as wide).
    - a row of 1024 keeps blocks of 512: one block as long as the row
      computes the whole square under the mask where four blocks skip a
      quarter of it, and in the cell's trace the kernels were no faster
      (+0.9 %), though alone they had measured 4-8 %.
    - another head dimension keeps blocks of 512: at head 64 the larger
      blocks measured 3 % of the forward and nothing of the backward, and
      over 192 nothing is measured and the tiles' VMEM grows with it.

    A row that 1024 does not cut evenly keeps 512 too; no block is longer
    than the row."""
    wide = head_dim in (128, 192) and kv_len >= 2048 \
        and kv_len % 1024 == 0
    block = 1024 if wide else 512
    return SplashBlocks(block, block, 512, block, block, 512).capped(kv_len)


def _splash_kernel(seq_len: int, n_heads: int, head_dim: int, causal: bool,
                   block_length: int = 0,
                   blocks: Optional[SplashBlocks] = None):
    """The splash kernel over ``seq_len`` positions, in ``blocks`` (capped at
    the row) or, by default, in what :func:`splash_blocks` picks for the row;
    and what the call costs a head, as the first-call record carries it:
    ``attn_calls`` (1), ``attn_blocks`` (the forward's with work),
    ``attn_blocks_cut`` (those of them that apply a mask: a stored tile
    read, or the mask computed from the positions), ``attn_grid_steps_fwd``
    and ``attn_grid_steps_bwd`` (of the forward and of the fused backward,
    skipped steps included), the blocks themselves (``attn_block_q``,
    ``attn_block_kv``, ``attn_block_q_bwd``, ``attn_block_kv_bwd``) and
    ``attn_dq_partials`` (the row over the backward's kv block).

    NOT cached: the kernel object built during one jit trace captures that
    trace's context (its mask arrays are constants of that trace) and reusing
    it from a later one raises UnexpectedTracerError.  What costs is the
    library's pass over the mask, a block at a time in numpy, and that it
    caches by the mask's value (``_process_mask``, twelve entries): the
    second trace of a mask pays nothing, a first one about a second for a
    block-diffusion row of 16384 (sandbox CPU)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
        splash_attention_mask_info as mi,
    )

    if block_length:
        mask = _block_diffusion_mask()(seq_len // 2, block_length)
    else:
        mask = (sm.CausalMask if causal else sm.FullMask)((seq_len, seq_len))
    mask = sm.MultiHeadMask([mask] * n_heads)
    interpret = jax.default_backend() != "tpu"
    b = blocks.capped(seq_len) if blocks else splash_blocks(seq_len, head_dim)
    bs = sk.BlockSizes(
        block_q=b.q, block_kv=b.kv, block_kv_compute=b.kv_compute,
        block_q_dkv=b.q_bwd, block_kv_dkv=b.kv_bwd,
        block_kv_dkv_compute=b.kv_bwd_compute,
        use_fused_bwd_kernel=True,
    )
    kernel = sk.make_splash_mha(mask, head_shards=1, q_seq_shards=1,
                                block_sizes=bs, interpret=interpret,
                                residual_checkpoint_name=SPLASH_RESIDUALS)
    # The kernel's own mask arrays are tracers here.  These are the library's
    # cached numpy originals, asked for as ``make_splash_mha`` asks (a cache
    # hit; were its call to change, a second pass over the mask).
    fwd, computed = mi.process_mask(
        mask, (b.q, b.kv), downcast_smem_data=True, head_shards=1,
        q_seq_shards=1)
    dkv, _ = mi.process_mask_dkv(
        mask, (b.q_bwd, b.kv_bwd), downcast_smem_data=True, head_shards=1,
        q_seq_shards=1, shrink_grid=False)
    work = fwd.block_mask[0] > 0
    # With a mask function the kernel computes the mask on every block it
    # runs; with stored tiles a whole block is told apart (``block_mask``
    # 2) and pays one ``or``.
    cut = work if computed is not None else fwd.block_mask[0] == 1
    return kernel, {
        "attn_calls": 1, "attn_blocks": int(work.sum()),
        "attn_blocks_cut": int(cut.sum()),
        "attn_grid_steps_fwd": int(np.prod(fwd.block_mask.shape[1:])),
        "attn_grid_steps_bwd": int(np.prod(dkv.block_mask.shape[1:])),
        "attn_block_q": b.q, "attn_block_kv": b.kv,
        "attn_block_q_bwd": b.q_bwd, "attn_block_kv_bwd": b.kv_bwd,
        "attn_dq_partials": seq_len // b.kv_bwd}


def block_diffusion_allowed(i, j, seq_len: int, block_length: int):
    """May query position ``i`` read key position ``j``?  Both index the 2S
    positions ``[noised ; clean]`` of a row of ``seq_len`` ids in blocks of
    ``block_length`` (``models/block_diffusion.py`` has the why, and hands
    this on as ``allowed``): a noised query reads the noised keys of its own
    block and the clean keys of earlier blocks; a clean query reads the clean
    keys of its own and earlier blocks; nothing else.  numpy or jax integer
    arrays that broadcast."""
    def block_of(at):
        # Evaluated in numpy while a step is traced (the einsum path's dense
        # mask, the splash path's stored tiles), never on the chip, whose
        # vector unit has no integer divide.
        return (at - seq_len * (at >= seq_len)) // block_length

    q_noised, k_noised = i < seq_len, j < seq_len
    bi, bj = block_of(i), block_of(j)
    return (q_noised & k_noised & (bi == bj)) \
        | (q_noised & ~k_noised & (bj < bi)) \
        | (~q_noised & ~k_noised & (bj <= bi))


@functools.cache
def _block_diffusion_mask():
    """The class of :func:`block_diffusion_allowed` as a lazy mask of the
    splash library (made on first use: the library is imported only where
    the kernel runs)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm,
    )

    class BlockDiffusionMask(sm.Mask):
        """The rule over the 2S positions of a row of ``seq_len`` ids.  Not a
        ``_ComputableMask``: the library then finds the whole blocks, which
        pay nothing for the mask in the kernel, and stores each distinct cut
        tile once (three where the kernel's block is a multiple of the block
        length), which the kernel reads.  No 2S x 2S array exists: the
        library asks for one block at a time."""

        def __init__(self, seq_len: int, block_length: int):
            self._key = (seq_len, block_length)

        @property
        def shape(self) -> Tuple[int, ...]:
            return (2 * self._key[0],) * 2

        def __getitem__(self, idx) -> np.ndarray:
            seq_len, block_length = self._key
            if len(idx) != 2 or not all(isinstance(s, slice) for s in idx):
                raise NotImplementedError(f"Unsupported slice: {idx}")
            # The rule reads only a position's copy and block, which one
            # number says (S is a multiple of the block length): decide each
            # pair of those once and spread the answer over the pairs of
            # positions.  A chunk is most often one answer: of the 4096 the
            # library asks for in a trace of ``sdar-ep8-s8192`` 192 are cut.
            def blocks_of(s: slice):
                at = np.arange(*s.indices(2 * seq_len)) // block_length
                return np.unique(at, return_inverse=True)

            (q_blocks, q_spread), (k_blocks, k_spread) = map(blocks_of, idx)
            small = block_diffusion_allowed(
                q_blocks[:, None] * block_length,
                k_blocks[None, :] * block_length, seq_len, block_length)
            if small.all() or not small.any():
                return np.full((q_spread.size, k_spread.size), small.all())
            return small[q_spread[:, None], k_spread[None, :]]

        # by value: the library's cache of processed masks hits on the next
        # trace, and the heads' masks count as one
        def __eq__(self, other: object):
            return isinstance(other, type(self)) and self._key == other._key

        def __hash__(self):
            return hash((type(self), self._key))

    return BlockDiffusionMask


def splash_attention(q, k, v, causal: bool = True,
                     sm_scale: Optional[float] = None,
                     block_length: int = 0):
    """Production TPU attention (splash kernel): sparse over the causal
    mask when causal (no wasted upper-triangle work), full-mask
    bidirectional (ViT-style) otherwise, with a fused dq/dkv backward.  With
    a ``block_length`` the sequence is a block-diffusion row's 2S positions
    and the mask :func:`block_diffusion_allowed` (``causal`` is not read).
    The kernel's blocks are :func:`splash_blocks`' for the row: no argument.

    q: (B, S, H, head_dim), k and v: (B, S, KV, head_dim), the model's native
    layout; H is a multiple of KV, and query head ``h`` attends to K/V head
    ``h // (H // KV)`` (``jnp.repeat``'s order).  KV == H is plain MHA.  v's
    head dimension may be its own (the library's ``head_dim_v``): the output
    has it, and the default scale and the blocks follow q's.

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
        kernel, counts = _splash_kernel(S, q.shape[2], hd, causal,
                                        block_length)
        first_call.note(**counts)
        # Splash takes (H, S, hd) per example; scale q up front (no scale arg).
        qt = (q * sm_scale).transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        return jax.vmap(kernel)(qt, kt, vt).transpose(0, 2, 1, 3)

    mesh = jax.sharding.get_abstract_mesh()
    KV = k.shape[2]
    tensor = 1 if mesh.empty else mesh.shape.get("tensor", 1)
    if H % tensor or KV % tensor:
        raise ValueError(
            f"splash_attention: the mesh's tensor axis ({tensor}) must divide "
            f"the {KV} K/V heads as it must divide the {H} query heads: a "
            "chip's query heads read only the K/V heads it holds")
    return place(local, (q, k, v), ("rh", "rh", "rh"), "rh")
