"""Block-diffusion training (SDAR): what a row of ids becomes before the
decoder sees it, which position may read which, and how the loss weighs
what was masked.  ``models/llama.py`` calls this when its configuration has
a ``block_length``; the mask itself is applied by
``ops.attention.block_diffusion_attention``.

A row ``x0`` of S ids is cut into blocks of ``block_length``.  The model's
input is ``[xt ; x0]``, 2S positions: ``xt`` is ``x0`` with some positions
replaced by the ``[MASK]`` id.  Position ``i`` of the input has the block
``b(i) = (i mod S) // block_length`` and, for RoPE, the position ``i mod S``.
A noised position sees its own block of the noised copy (both directions)
and the earlier blocks of the clean copy; a clean position sees the clean
copy block-causally and never the noised one (:func:`allowed`).  A masked
position of the noised copy predicts the id it covers: no shift.

Noise, per block: ``m`` uniform on {0 .. block_length}, then a uniformly
random subset of ``m`` positions (what masking each position independently at
a rate t ~ U(0, 1) gives, in its count form).  The loss is the mean, over the
blocks with a masked position, of the block's mean cross-entropy over its
masked positions: weight ``1 / m`` on a masked position, the sum divided by
the number of such blocks.  The weight is a function of the mask pattern and
stays in [1 / block_length, 1].

The draw is a pure function of the row's ids and ``noise_seed``, so that
anything else holding the row can draw the same mask: per row the key
``fold_in(key(noise_seed), sum(row) mod 2^31)``, split in two; the first
half draws ``m`` (``randint``), the second 32 random bits a position, and a
position is masked when fewer than ``m`` positions of its block drew fewer
bits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the mask's rule, ``allowed(i, j, seq_len, block_length)``: it lives with the
# kernel that computes it tile by tile (``ops`` imports no model)
from ray_tpu.ops.attention import block_diffusion_allowed as allowed  # noqa: F401


def masked_positions(tokens, noise_seed: int, block_length: int):
    """tokens: (B, S) ids, S a multiple of ``block_length``.  -> (masked
    (B, S) bool, m (B, S / block_length) int32: how many each block lost)."""
    S = tokens.shape[-1]
    assert S % block_length == 0, (S, block_length)
    blocks = S // block_length

    def row(ids):
        data = jnp.sum(ids.astype(jnp.uint32)) % jnp.uint32(2 ** 31)
        k_m, k_bits = jax.random.split(
            jax.random.fold_in(jax.random.key(noise_seed), data))
        m = jax.random.randint(k_m, (blocks,), 0, block_length + 1)
        bits = jax.random.bits(k_bits, (blocks, block_length), jnp.uint32)
        rank = jnp.sum(bits[:, None, :] < bits[:, :, None], axis=-1)
        return (rank < m[:, None]).reshape(S), m

    return jax.vmap(row)(tokens)


def loss_weights(masked, m):
    """-> (B, S) float32: ``1 / m`` on a masked position of a block that
    lost ``m``, 0 elsewhere, over the number of blocks that lost any, so that
    ``sum(weights * cross_entropy)`` is the loss."""
    block_length = masked.shape[-1] // m.shape[-1]
    per_block = jnp.where(m > 0, 1.0 / jnp.maximum(m, 1), 0.0)
    weights = masked * jnp.repeat(per_block, block_length, axis=-1)
    return weights / jnp.maximum(jnp.sum(m > 0), 1)


def noise(tokens, noise_seed: int, block_length: int, mask_id: int):
    """tokens: (B, S) clean ids.  -> (the model's input ``[xt ; x0]``
    (B, 2S), the loss weights (B, S) of the noised copy's positions)."""
    with jax.named_scope("noise"):
        masked, m = masked_positions(tokens, noise_seed, block_length)
        xt = jnp.where(masked, mask_id, tokens)
        return (jnp.concatenate([xt, tokens], axis=-1),
                loss_weights(masked, m))
