"""The dense LM-head loss the decoders share."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def lm_head_cross_entropy(x, head, targets, logits_dtype, weights=None):
    """Mean token cross-entropy of the logits ``x @ head.T``; with
    ``weights`` (B, S) the sum of each position's cross-entropy times its
    weight (the caller's weights say what mean that is).

    x: (B, S, D) final hidden states; head: (V, D) in the compute dtype (a
    tied head passes the embedding); targets: (B, S) int.  The (B, S, V)
    logits materialize in ``logits_dtype``, the step's largest tensor; the
    reductions run in float32 whatever that is.  Log-sum-exp less the target
    logit, not log_softmax, keeps the traffic over the logits to one
    reduction pass (~2 MFU points on v5e for the 124M model, r3).
    """
    logits = jnp.einsum("bsd,vd->bsv", x, head,
                        preferred_element_type=logits_dtype)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0].astype(jnp.float32)
    if weights is None:
        return jnp.mean(lse - tgt)
    return jnp.sum((lse - tgt) * weights)
