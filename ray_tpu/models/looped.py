"""A looped decoder's training loss (Ouro): the stack of layers is run
``T = ut_steps`` times over with one set of weights, every pass ends in the
shared final norm, the shared head and an exit gate, and the loss is an
expectation over the pass a position would leave at.  ``models/llama.py``
runs the passes (``forward_hidden``) and calls this where its configuration
has ``ut_steps`` > 1; at 1 nothing here is traced.

Per position, with ``h^(t)`` the normed state after pass ``t``:

    lam^(t) = sigmoid(h^(t) . w + b)                   the gate, float32
    q_t     = lam^(t) prod_{j<t} (1 - lam^(j))         t < T
    q_T     = prod_{j<T} (1 - lam^(j))                 what is left
    CE_t    = logsumexp(logits^(t)) - logits^(t)[target]
    loss    = mean_i [ sum_t q_t CE_t - beta H(q) ],   H(q) = -sum_t q_t log q_t

(the entropy-regularised expectation under a uniform prior over the exit
pass; ``q`` clipped below at 1e-20 inside the log).  The last pass's gate
decides nothing: what has not left by then leaves there.  Gradients reach
the gate through ``q`` and every pass up to ``t`` through ``CE_t``.

The head runs a pass at a time, in a ``lax.scan`` over the passes' states:
``ops.lm_head.lm_head_cross_entropy_by_position`` hands back (B, S) and
keeps no logits, so a step holds one pass's logits and their cotangent at a
time, forward and backward (1.1 GiB of temporaries for the heads at 8192 x
49152 where four passes' logits kept by autodiff take 3.2; PERF.md PR 65).
The gate's product rides in the same scan;
the sigmoids, ``q``, the entropy and the combination follow it, all in
float32 under the scope ``exit_gate``.

Two step counters leave with the loss (``tracing.STEP_COUNTER_REGISTRY``):
``loss_ut``, each pass's mean cross-entropy, and ``ut_exit_mass``, each
pass's mean ``q_t``, both float32 (T,).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.lm_head import lm_head_cross_entropy_by_position
from ray_tpu.util.tracing import step_counter

def init_gate(d_model: int):
    """The exit gate, ``d_model`` -> 1 with a bias, as one vector of
    ``d_model + 1``, the weight and then the bias, all zero: ``lam`` is 1/2
    on every position at the start, ``q`` is (1/2, 1/4, ..) and every pass's
    head trains from the first step.  Zero and not a small normal draw: the
    normed state the gate reads has a component every position shares, a
    drawn weight's product with it grows as that component does, and within
    tens of steps the gate sat at 0.99 and beyond on one pass (on the chip
    at Ouro-2.6B's widths, PERF.md PR 65), where the other passes' heads
    train on nothing and the gate's own gradient is the rounding of its
    input.  From zero the logit moves by what the optimizer moves the
    weight, a learning rate a step at most.

    One leaf and not two: the bias's gradient is a sum over positions of
    terms that sum to zero over the passes, a scalar that cancels to nothing
    where the passes' losses meet, and whatever goes leaf by leaf (a
    comparison with a reference by a leaf's largest entry, a clip by a
    leaf's norm) would divide by it."""
    return jnp.zeros((d_model + 1,), jnp.float32)


GATE_AXES = ("norm",)


def exit_distribution(lam):
    """lam: (T - 1, ...) the first T - 1 passes' gates in (0, 1).  -> q
    (T, ...): the probability of leaving at each pass, summing to one."""
    stay = jnp.cumprod(1.0 - lam, axis=0)  # prod_{j<=t} (1 - lam_j)
    reached = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([lam * reached, stay[-1:]], axis=0)


def expected_loss(ce, lam, beta: float):
    """ce: (T, B, S) each pass's cross-entropy a position; lam: (T - 1, B,
    S).  -> (the loss, q (T, B, S))."""
    q = exit_distribution(lam)
    entropy = -jnp.sum(q * jnp.log(jnp.maximum(q, 1e-20)), axis=0)
    return jnp.mean(jnp.sum(q * ce, axis=0) - beta * entropy), q


def loss_and_counters(xs, params, targets, config
                      ) -> Tuple[Any, Dict[str, Any]]:
    """xs: (T, B, S, D) the normed state after each pass, the compute
    dtype; of ``params`` the head and the gate; of ``config`` ``dtype``,
    ``logits_dtype`` and ``exit_beta``.  -> (the loss, its counters)."""
    head = params["lm_head"].astype(config.dtype)
    gate = params["exit_gate"]

    def one_pass(_, x):
        with jax.named_scope("lm_head"):
            ce = lm_head_cross_entropy_by_position(x, head, targets,
                                                   config.logits_dtype)
        with jax.named_scope("exit_gate"):
            z = jnp.sum(x.astype(jnp.float32) * gate[:-1], axis=-1) \
                + gate[-1]
        return None, (ce, z)

    _, (ce, z) = lax.scan(one_pass, None, xs)
    with jax.named_scope("exit_gate"):
        loss, q = expected_loss(ce, jax.nn.sigmoid(z[:-1]), config.exit_beta)
        return loss, {
            step_counter("loss_ut"): jnp.mean(ce, axis=(1, 2)),
            step_counter("ut_exit_mass"): jnp.mean(q, axis=(1, 2))}
