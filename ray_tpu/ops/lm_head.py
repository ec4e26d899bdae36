"""The dense LM-head loss the decoders share: the mean form, and a
per-position form for a loss that weighs each position's cross-entropy by
something it differentiates (``models/looped.py``)."""

from __future__ import annotations

from functools import partial

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
    lse, tgt = _lse_and_target(x, head, targets, logits_dtype)
    if weights is None:
        return jnp.mean(lse - tgt)
    return jnp.sum((lse - tgt) * weights)


def _lse_and_target(x, head, targets, logits_dtype):
    """(each position's log-sum-exp over the logits ``x @ head.T``, its
    target's logit), float32 (B, S) each; the logits in ``logits_dtype``."""
    logits = jnp.einsum("bsd,vd->bsv", x, head,
                        preferred_element_type=logits_dtype)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0].astype(jnp.float32)
    return lse, tgt


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def lm_head_cross_entropy_by_position(x, head, targets, logits_dtype):
    """Each position's cross-entropy under the logits ``x @ head.T``,
    (B, S) float32: :func:`lm_head_cross_entropy`'s arithmetic before its
    mean, for a caller whose weights on the positions take a gradient
    themselves (an exit gate's distribution over several passes' heads).

    Nothing (B, S, V) wide outlives the call: the backward is handed x,
    the head, the targets and the (B, S) log-sum-exps, makes the logits
    again (one product more, none of the reductions) and from them the
    cotangent ``(softmax - onehot) * g`` in ``logits_dtype``, so a step
    that calls this several times holds one call's logits and their
    cotangent at a time and not every call's from its forward to its
    backward."""
    return _by_position_fwd(x, head, targets, logits_dtype)[0]


def _by_position_fwd(x, head, targets, logits_dtype):
    lse, tgt = _lse_and_target(x, head, targets, logits_dtype)
    return lse - tgt, (x, head, targets, lse)


def _by_position_bwd(logits_dtype, kept, g):
    x, head, targets, lse = kept
    logits = jnp.einsum("bsd,vd->bsv", x, head,
                        preferred_element_type=logits_dtype)
    probs = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    hit = jnp.arange(head.shape[0]) == targets[..., None]
    dlogits = ((probs - hit) * g[..., None]).astype(logits_dtype)
    dx = jnp.einsum("bsv,vd->bsd", dlogits, head,
                    preferred_element_type=x.dtype)
    dhead = jnp.einsum("bsv,bsd->vd", dlogits, x,
                       preferred_element_type=head.dtype)
    return dx, dhead, None


lm_head_cross_entropy_by_position.defvjp(_by_position_fwd, _by_position_bwd)
