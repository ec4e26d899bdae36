"""The dropless mixture-of-experts layer: what replaces the SwiGLU MLP in a
``models/llama.py`` block when the configuration has experts (OLMoE-1B-7B: 64
SwiGLU experts of width 1024, 8 a token).  This file is the layer and nothing
else; embedding, attention, the layer scan, the loss and the train step are
``llama.py``'s.

Per token: router logits and their softmax in float32, the ``k`` largest
probabilities pick the experts and are the combine weights (renormalised only
where the model's ``norm_topk_prob`` says so).  Then, with N tokens:

1. the N x k (token, slot) pairs are sorted by expert (stable) and the E group
   sizes counted;
2. the pairs' rows are gathered into that order, (N x k, D), and their
   combine weights brought into it, (N x k,);
3. three grouped matmuls over the ragged groups (``ops/grouped_matmul.py``):
   gate and up, ``silu(gate) * up * weight`` in one float32 pass, down.  The
   weight multiplies before the down-projection, not after it: the same sum
   reassociated, ``w (h W) = (w h) W``;
4. the weighted rows go back to token order and each token sums its k rows in
   float32.

Static shapes throughout (exactly N x k rows), no capacity and no pair
dropped.  The dispatch (2) and the combine (4) are each other's transposes,
one a row gather by ``order // k`` and the other a gather by ``inverse``
summed over k, and each is written as the other's backward (``custom_vjp``):
neither direction holds a scatter-add, both save the two index vectors and
nothing else, and both row gathers by ``order // k`` read an (N, D) source.
The weights' permutation and its transpose are sorts.  Because the weights
meet the rows in expert order, nothing in the backward reads the
down-projection's output: the weights' gradient is a row sum inside the
activation's backward pass, and under ``jax.checkpoint`` the down-projection
and the combine are not run a second time.

Two router losses come back with the output, for ``llama.loss_fn`` to weigh
(:func:`router_losses`).

Under a mesh tokens never leave their chip: the whole layer runs inside a
``shard_map`` over the mesh's batch axes, as ``ops.attention.splash_attention``
does, because a global sort over a sharded batch would gather the batch and a
Mosaic call cannot be partitioned.  The only numbers that cross chips are the
router losses' 2E + 1 means (one ``pmean``).  The router's and the experts'
weights enter that ``shard_map`` replicated: however they are stored (``embed`` over `fsdp`, the expert axis
over `expert`), XLA gathers them on the way in and reduces their gradients on
the way out, which is FSDP's meaning.

**A chip's share of the experts** (``experts_held``, a run of expert ids): what
one chip of an expert-parallel job holds of a layer.  The router keeps its
published width and its experts a token, all N x k pairs are sorted as above,
and the grouped products run over the held groups only (``rhs`` is the held
experts' matrices, the kernels' ``group_offset``): rows of an absent expert
come out zero, cost no product, and add nothing in the combine.  Parameters,
gradients and optimizer state exist for the held experts only.  What is not
built is the exchange: the ragged all-to-all that would bring this chip the
other chips' rows for its experts and send its own rows to theirs.  On one
chip the layer computes its own experts' part for its own tokens, and what
the absent experts would add is left out.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import remat
from ray_tpu.ops.grouped_matmul import grouped_matmul


def router_losses(logits, probs, experts, batch_axes=()
                  ) -> Tuple[jax.Array, jax.Array]:
    """One layer's (load-balance, z) losses.  ``logits``, ``probs``:
    (..., E) float32; ``experts``: (..., k) the chosen ids.  Inside a
    ``shard_map`` whose ``batch_axes`` divide the tokens evenly, the means
    are taken over all of them.

    load-balance = E x sum_e f_e P_e, with f_e the share of the (token,
    slot) pairs that went to expert e and P_e the mean router probability of
    e over the tokens: 1.0 under a uniform router, E when one expert takes
    everything.  z = mean over tokens of logsumexp(logits)^2."""
    E = logits.shape[-1]
    chosen = experts[..., None] == jnp.arange(E, dtype=experts.dtype)
    f = jnp.mean(chosen.astype(jnp.float32),
                 axis=tuple(range(chosen.ndim - 1)))
    p = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    if batch_axes:
        f, p, z = lax.pmean((f, p, z), batch_axes)
    return E * jnp.sum(f * p), z


def route(h32, router_w, k: int, norm_topk_prob: bool, batch_axes=()):
    """h32: (..., D) float32.  -> combine weights (..., k) float32, expert ids
    (..., k) int32, (load-balance, z).  The matmul runs at full float32
    precision (the TPU's default would round its operands to bfloat16, and a
    token's k-th expert is decided by the last bits)."""
    logits = jnp.einsum("...d,de->...e", h32, router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = lax.top_k(probs, k)
    losses = router_losses(logits, probs, experts, batch_axes)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts, losses


def sort_pairs(experts, n_experts: int):
    """experts: (N, k) ids.  -> ``order`` (N x k,): the flat (token, slot)
    pairs in expert order, ties in token order; ``inverse`` (N, k): where
    each pair went; ``group_sizes`` (E,) int32, summing to N x k."""
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32).reshape(experts.shape)
    group_sizes = jnp.sum(
        flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype), axis=0,
        dtype=jnp.int32)
    return order, inverse, group_sizes


@jax.custom_vjp
def _to_expert_order(x, order, inverse):
    """x: (N, D) -> (N x k, D), row j the token of pair ``order[j]``."""
    return x[order // inverse.shape[1]]


@jax.custom_vjp
def _combine(rows, order, inverse):
    """rows: (N x k, D) in expert order, already weighted -> (N, D), each
    token the float32 sum of its k rows."""
    return jnp.sum(rows[inverse].astype(jnp.float32),
                   axis=1).astype(rows.dtype)


# The two are each other's transposes, so each is the other's backward.
_to_expert_order.defvjp(
    lambda x, order, inverse: (_to_expert_order(x, order, inverse),
                               (order, inverse)),
    lambda saved, g: (_combine(g, *saved), None, None))
_combine.defvjp(
    lambda rows, order, inverse: (_combine(rows, order, inverse),
                                  (order, inverse)),
    lambda saved, g: (_to_expert_order(g, *saved), None, None))


@jax.custom_vjp
def _weights_to_expert_order(weights, order, inverse):
    """weights: (N, k) -> (N x k,), entry j the weight of pair ``order[j]``.
    Each direction applies its permutation as a sort keyed by the other one:
    0.06 ms for the cell's 65,536 scalars on a v5e, where the gather
    ``weights.reshape(-1)[order]`` and its transpose, as a gather by
    ``inverse`` or as a scatter-add, take 0.47-0.57 each (``PERF.md``,
    PR 29)."""
    return lax.sort((inverse.reshape(-1), weights.reshape(-1)),
                    num_keys=1)[1]


def _weights_to_expert_order_fwd(weights, order, inverse):
    return _weights_to_expert_order(weights, order, inverse), (order, inverse)


def _weights_to_expert_order_bwd(saved, g):
    order, inverse = saved
    return (lax.sort((order, g), num_keys=1)[1].reshape(inverse.shape),
            None, None)


_weights_to_expert_order.defvjp(_weights_to_expert_order_fwd,
                                _weights_to_expert_order_bwd)


def expert_mlp(x, weights, experts, w_gate, w_up, w_down, n_experts=None,
               first_held: int = 0):
    """One chip's tokens through their experts.  x: (N, D); weights,
    experts: (N, k); w_gate, w_up: (H, D, F); w_down: (H, F, D), all in the
    compute dtype: the matrices of experts ``first_held .. first_held + H``
    of ``n_experts`` (default H: every expert is held).  -> (N, D)."""
    held = w_gate.shape[0]
    n_experts = n_experts or held
    with jax.named_scope("moe_dispatch"):
        order, inverse, group_sizes = sort_pairs(experts, n_experts)
        rows = _to_expert_order(x, order, inverse)
        w_rows = _weights_to_expert_order(weights, order, inverse)
    # the products' scope says whether the layer holds a share
    with jax.named_scope("experts") if held == n_experts \
            else jax.named_scope("moe_held"):
        gate = checkpoint_name(
            grouped_matmul(rows, w_gate, group_sizes, first_held),
            remat.GATE_UP)
        up = checkpoint_name(
            grouped_matmul(rows, w_up, group_sizes, first_held),
            remat.GATE_UP)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)
               * w_rows[:, None].astype(jnp.float32)).astype(x.dtype)
        out = grouped_matmul(act, w_down, group_sizes, first_held)
    with jax.named_scope("moe_dispatch"):
        return _combine(out, order, inverse)


def moe_mlp(h32, blk, *, experts_per_token: int, norm_topk_prob: bool, dtype,
            first_held: int = 0):
    """The layer.  h32: (B, S, D) float32, the block's normed input (the
    router reads it as it is, the experts its cast to ``dtype``); ``blk``
    holds ``router`` (D, E) and the matrices of the H experts from
    ``first_held`` on, ``w_gate``, ``w_up`` (H, D, F), ``w_down`` (H, F, D);
    H = E unless the chip holds a share.  -> (y (B, S, D) in ``dtype``,
    (load-balance, z)), the losses over all E experts."""
    mesh = jax.sharding.get_abstract_mesh()
    sharded = not (mesh.empty or mesh.size == 1)
    batch_axes = tuple(a for a in ("data", "fsdp")
                       if sharded and a in mesh.axis_names)

    def local(h32, router, w_gate, w_up, w_down):
        tokens = h32.reshape(-1, h32.shape[-1])
        with jax.named_scope("router"):
            weights, experts, losses = route(
                tokens, router, experts_per_token, norm_topk_prob, batch_axes)
        y = expert_mlp(tokens.astype(dtype), weights, experts, w_gate, w_up,
                       w_down, router.shape[-1], first_held)
        return y.reshape(h32.shape), losses

    args = (h32, blk["router"], blk["w_gate"].astype(dtype),
            blk["w_up"].astype(dtype), blk["w_down"].astype(dtype))
    if not sharded:
        return local(*args)
    P = jax.sharding.PartitionSpec
    rows = P(batch_axes or None, None, None)
    # check_vma off as for splash: a pallas_call declares no vma on its
    # outputs.  Axes a spec does not name (the weights' every axis) see
    # whole arrays.
    return jax.shard_map(local, in_specs=(rows, P(), P(), P(), P()),
                         out_specs=(rows, (P(), P())), check_vma=False)(*args)
