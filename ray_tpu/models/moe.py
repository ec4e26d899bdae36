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

**A layer decides its routing once a step.**  What the router decided bears
one name, ``remat.ROUTING``, which the layer's ``jax.checkpoint`` always
keeps (``ops/remat.py``; :func:`routing_bytes`, a few MB a layer): the
logits, the chosen ids and their scores (:func:`route`), the sorted order,
its inverse and the group sizes (:func:`sort_pairs`), the weights in expert
order.  The backward reads the forward's own and makes no full-precision
product, ``top_k`` or sort a second time, so it cannot pick another k-th
expert than the forward did; what it makes again of these is elementwise on
(N, E) or (N, k).

Two router losses come back with the output, for ``llama.loss_fn`` to weigh
(:func:`router_losses`), and a count: the rows that reached each expert held
here, (shards, H) int32, the held slice of the sort's ``group_sizes``.  It is
what the grouped kernels' time follows; ``llama.make_train_step`` carries it
out of the compiled step as the step counter ``moe_rows``
(``util/tracing.py``), and beside it ``moe_moved`` where the layer holds a
share (below): the two facts of the layer that the data decide.

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
published width and its experts a token, and all N x k pairs are sorted as
above; in that order the pairs of the held experts are one run, and only the
run is moved.  It is walked in windows of a static bound
(:data:`WINDOW_SHARE` of the pairs): a window's rows are gathered,
multiplied, activated and summed back into token order (on the chip by a
kernel that reads the window's rows once, ``ops/window_return.py``), and how
many windows a step walks is decided on the device by the step's own count
of held rows (a loop of that length; eight eighths are the move of every
pair, so no pair is dropped: the rows never walked were zeros in the
combine's float32 sum).  The kernels see the held experts' groups, cut to
the window, between two groups
that ``rhs`` does not hold (their ``group_offset``), the window's rows before
and after its part of the run: those rows come out zero and cost no product.
One set of kernels at the window's size serves every step; the rows a layer
moved leave the step as ``moe_moved``.  Where every expert is held the run
is every pair and the layer moves them at once, with no loop.  Parameters,
gradients and optimizer state exist for the held experts only.  What is not
built is the exchange: the ragged all-to-all that would bring this chip the
other chips' rows for its experts and send its own rows to theirs.  On one
chip the layer computes its own experts' part for its own tokens, and what
the absent experts would add is left out.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import placement, remat, window_return
from ray_tpu.ops.grouped_matmul import grouped_matmul
from ray_tpu.util import first_call
from ray_tpu.util.tracing import step_counter


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


def _scores_at(scores, experts):
    """scores: (..., E), experts: (..., k) ids -> (..., k), the scores at
    those ids: ``take_along_axis`` as a comparison, a select and a sum over
    E of one score and zeros, so exact, and its transpose the same over k.
    On the v5e both fuse into their neighbours' pass, where the gather of
    16,384 x 8 scalars out of 128 a row took 1.0 ms and its transpose, a
    scatter-add, 1.1 (``PERF.md``, PR 48)."""
    hot = experts[..., None] == jnp.arange(scores.shape[-1],
                                           dtype=experts.dtype)
    return jnp.sum(jnp.where(hot, scores[..., None, :], 0), axis=-1)


def route(h32, router_w, k: int, norm_topk_prob: bool, batch_axes=(),
          scoring: str = "softmax", bias=None, scale: float = 1.0):
    """h32: (..., D) float32.  -> combine weights (..., k) float32, expert ids
    (..., k) int32, (load-balance, z).  The matmul runs at full float32
    precision (the TPU's default would round its operands to bfloat16, and a
    token's k-th expert is decided by the last bits).

    **Decided once a step.**  The logits, the chosen ids and their scores
    bear ``remat.ROUTING``, which a layer's ``jax.checkpoint`` always keeps
    (``ops/remat.py``): the backward reads the forward's own routing and
    makes neither the product nor the ``top_k`` a second time; what it makes
    again of these is elementwise.  The ids are ``top_k``'s of a value that
    carries no gradient and the scores are read at them (:func:`_scores_at`:
    ``top_k``'s values to the bit), because ``top_k``'s own derivative reads
    the ids it made itself, before any name.

    ``scoring`` is how a logit becomes a weight.  ``"softmax"``: the k
    largest probabilities, the two router losses over them.  ``"sigmoid"``
    (Nemotron-3, DeepSeek-V3): every expert scored by itself; the k experts
    are the largest of ``score + bias``, with ``bias`` (E,) a selection bias
    that balances the load and is no parameter (it picks the experts, does
    not weigh them, and gets no gradient), the weights the scores at those
    experts **without** it; such a router names no auxiliary loss, and the
    two losses come back zero.  Either way ``norm_topk_prob`` divides the k
    weights by their sum and ``scale`` multiplies them
    (``routed_scaling_factor``)."""
    logits = checkpoint_name(
        jnp.einsum("...d,de->...e", h32, router_w.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST), remat.ROUTING)
    if scoring == "softmax":
        scores = chosen_by = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        chosen_by = scores if bias is None else \
            scores + bias.astype(jnp.float32)
    else:
        raise ValueError(f"router scoring {scoring!r} (softmax|sigmoid)")
    experts = checkpoint_name(
        lax.top_k(lax.stop_gradient(chosen_by), k)[1], remat.ROUTING)
    weights = checkpoint_name(_scores_at(scores, experts), remat.ROUTING)
    losses = router_losses(logits, scores, experts, batch_axes) \
        if scoring == "softmax" else (jnp.zeros((), jnp.float32),) * 2
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts, losses


def sort_pairs(experts, n_experts: int):
    """experts: (N, k) ids.  -> ``order`` (N x k,): the flat (token, slot)
    pairs in expert order, ties in token order; ``inverse`` (N, k): where
    each pair went; ``group_sizes`` (E,) int32, summing to N x k.  All three
    bear ``remat.ROUTING``: the backward reads these and sorts nothing."""
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32).reshape(experts.shape)
    group_sizes = jnp.sum(
        flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype), axis=0,
        dtype=jnp.int32)
    return tuple(checkpoint_name(a, remat.ROUTING)
                 for a in (order, inverse, group_sizes))


def routing_bytes(tokens: int, n_experts: int, k: int) -> int:
    """The bytes of what one layer over ``tokens`` positions names
    ``remat.ROUTING``: the logits, (N, E); the ids, their scores and
    ``inverse``, (N, k); ``order`` and the weights in it, (N x k,);
    ``group_sizes``, (E,); four bytes each.  (Of a name the checkpoint
    keeps what the backward reads: not the scores where nothing divides
    them by their sum.)"""
    return 4 * (tokens * (n_experts + 5 * k) + n_experts)


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


#: The rows an expert layer that holds a share moves at a time, as a share of
#: the N x k pairs it sorts: the run of the sorted pairs that meets its
#: experts is walked in windows of this many rows, as many as the step's own
#: count needs.  An eighth: three (layer, step)s in five of sixteen held
#: experts of 128 need one window and nine in ten at most two (``PERF.md``, PR
#: 36), and at the cell's sizes a window of an eighth is the 64 MiB from which
#: the combine's gather runs three times as fast as from 128 (``PERF.md``, PR
#: 38).
WINDOW_SHARE = 1 / 8


def window_rows(pairs: int) -> int:
    """The rows of one window of a layer that sorts ``pairs`` rows."""
    return max(1, int(pairs * WINDOW_SHARE))


@jax.custom_vjp
def _to_window(x, pairs, inverse, run):
    """x: (N, D) -> (R, D), row j the token of pair ``pairs[j]``; ``pairs``
    is a window of ``order``, R entries that begin at place ``first - lead``
    of it, and ``run`` = (first, stop, lead) says which places of the order
    are the window's own and meet a held expert."""
    return x[pairs // inverse.shape[1]]


@jax.custom_vjp
def _from_window(rows, pairs, inverse, run):
    """rows: (R, D) on that window, already weighted -> (N, D), each token
    the float32 sum of those of its k rows that lie in the run, rounded
    once; a row of the window outside the run met no expert and is left
    out, whatever it holds.  Two forms, chosen while tracing from the
    backend, the mesh and the shapes (``ops/window_return.py:path``; the
    first-call record's ``moe_return`` says which).  On the chip a kernel
    that reads the window's R rows once (:mod:`~ray_tpu.ops.window_return`).
    Elsewhere a gather by ``inverse`` over all k x N slots and a masked sum
    of them, k x N x D bytes written and read whatever R is: the gathered
    rows lie slot-major, (k, N, D), because with the k slots between N and
    D they sit on the tiled second-minor axis, and where k is no multiple
    of the sublane tile the layout pads them through a copy of the whole
    array (6 -> 8, 10 -> 16: ``PERF.md``, PR 55)."""
    R, (N, k), D = rows.shape[0], inverse.shape, rows.shape[1]
    how = window_return.path(R, N, D, jax.sharding.get_abstract_mesh())
    first_call.entry(
        "moe_return", f"{R}x{N}x{k}x{D}",
        (how, window_return.tile(R, N, D)[0] if how == "kernel" else None))
    if how == "kernel":
        return window_return.from_window(rows, pairs, inverse, run)
    first, stop, lead = run
    slots = inverse.T
    inside = (slots >= first) & (slots < stop)
    place = jnp.clip(slots - (first - lead), 0, R - 1)
    return jnp.sum(jnp.where(inside[..., None], rows[place], 0)
                   .astype(jnp.float32), axis=0).astype(rows.dtype)


# Each other's transposes on the rows of the run, as the full move's pair is
# on every row; what the window's other rows get, no product reads.
_to_window.defvjp(
    lambda x, *window: (_to_window(x, *window), window),
    lambda window, g: (_from_window(g, *window), None, None, None))
_from_window.defvjp(
    lambda rows, *window: (_from_window(rows, *window), window),
    lambda window, g: (_to_window(g, *window), None, None, None))


def relu2(x):
    """relu(x)^2, the activation of Nemotron's two-matrix experts."""
    return jnp.square(jax.nn.relu(x))


def _through_experts(rows, w_rows, matrices, sizes, first, activation):
    """rows: (M, D) in expert order, w_rows: (M,) their combine weights ->
    (M, D), over the groups ``sizes``, whose matrices are those from group
    ``first`` on.  ``matrices`` = (w_gate, w_up, w_down): gate and up, the
    weighted ``activation(gate) * up``, down (SwiGLU with ``silu``); or
    (w_up, w_down), an expert without a gate: up, the weighted
    ``activation(up)``, down."""
    *w_in, w_down = matrices
    hidden = [checkpoint_name(grouped_matmul(rows, w, sizes, first),
                              remat.GATE_UP) for w in w_in]
    act = activation(hidden[0].astype(jnp.float32))
    if len(hidden) == 2:
        act = act * hidden[1].astype(jnp.float32)
    act = (act * w_rows[:, None].astype(jnp.float32)).astype(rows.dtype)
    return grouped_matmul(act, w_down, sizes, first)


def _move_window(static, c, x, w_sorted, matrices, order, inverse,
                 group_sizes):
    """Window ``c`` of the held experts' run through the layer: the
    :func:`window_rows` places of the sorted order from ``c`` windows behind
    the run's first (from earlier where the order ends sooner; those rows
    are an earlier window's) -> (N, D), what its pairs add to each token.
    The kernels see the held experts' groups, cut to the window, between two
    groups that ``rhs`` does not hold, the window's rows before and after
    its part of the run: those rows come out zero and no tile of theirs is
    visited."""
    first_held, activation = static
    bound = window_rows(order.shape[0])
    with jax.named_scope("moe_dispatch"):
        held_rows = group_sizes[first_held:first_held + matrices[-1].shape[0]]
        start = jnp.sum(group_sizes[:first_held])  # of the run
        stops = start + jnp.cumsum(held_rows)      # of each held group
        first = start + c * bound
        stop = jnp.minimum(first + bound, stops[-1])
        lead = jnp.maximum(first + bound - order.shape[0], 0)
        cut = jnp.maximum(jnp.minimum(stops, stop)
                          - jnp.maximum(stops - held_rows, first), 0)
        sizes = jnp.concatenate([lead[None], cut,
                                 (bound - lead - jnp.sum(cut))[None]])
        window = (lax.dynamic_slice(order, (first - lead,), (bound,)),
                  inverse, (first, stop, lead))
        rows = _to_window(x, *window)
        w_rows = lax.dynamic_slice(w_sorted, (first - lead,), (bound,))
    with jax.named_scope("moe_held"):
        out = _through_experts(rows, w_rows, matrices, sizes, 1, activation)
    with jax.named_scope("moe_dispatch"):
        return _from_window(out, *window)


def _sum(a, b):
    """a + b made in float32, in a's dtype."""
    return (a.astype(jnp.float32) + b.astype(jnp.float32)).astype(a.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_move(static, windows, x, w_sorted, matrices, order, inverse,
               group_sizes):
    """A share's layer behind the sort: the first ``windows`` windows of the
    held run, one after the other: a loop whose length the step's own count
    sets.  ``static`` = (the first held expert's id, the activation);
    ``matrices`` as :func:`_through_experts` takes them.  The backward is a
    second such loop whose pass runs its window
    again as far as the backward reads it and then backwards: what a window
    keeps lives inside its pass, and the layer saves its arguments and
    nothing else.  What the windows add up to, each token's rows and every
    gradient, is carried in the dtype the layer hands it on in, each
    addition made in float32 (two windows: the float32 sum, rounded once)."""
    def add(c, y):
        return _sum(y, _move_window(static, c, x, w_sorted, matrices, order,
                                    inverse, group_sizes))

    return lax.fori_loop(0, windows, add, jnp.zeros_like(x))


def _held_move_fwd(static, windows, *args):
    return _held_move(static, windows, *args), (windows, args)


def _held_move_bwd(static, saved, g):
    windows, (*floats, order, inverse, group_sizes) = saved

    def add(c, grads):
        return jax.tree.map(_sum, grads, jax.vjp(
            lambda *floats: _move_window(static, c, *floats, order, inverse,
                                         group_sizes),
            *floats)[1](g))

    return (None, *lax.fori_loop(0, windows, add, jax.tree.map(
        jnp.zeros_like, tuple(floats))), None, None, None)


_held_move.defvjp(_held_move_fwd, _held_move_bwd)


def expert_mlp(x, weights, experts, w_gate, w_up, w_down, n_experts=None,
               first_held: int = 0, activation=jax.nn.silu):
    """One chip's tokens through their experts.  x: (N, D); weights,
    experts: (N, k); w_gate, w_up: (H, D, F); w_down: (H, F, D), all in the
    compute dtype: the matrices of experts ``first_held .. first_held + H``
    of ``n_experts`` (default H: every expert is held); ``w_gate`` None for
    experts of two matrices, ``down(activation(up(x)))``.  -> ((N, D); the
    pairs that reached each held expert, (H,) int32; the rows the layer
    moved, int32, :func:`window_rows` for each window the step's count
    needed: ``None`` where every expert is held and the layer moves every
    pair at once)."""
    matrices = (w_up, w_down) if w_gate is None else (w_gate, w_up, w_down)
    held = w_up.shape[0]
    n_experts = n_experts or held
    with jax.named_scope("moe_dispatch"):
        order, inverse, group_sizes = sort_pairs(experts, n_experts)
        w_rows = checkpoint_name(
            _weights_to_expert_order(weights, order, inverse), remat.ROUTING)
        held_rows = group_sizes[first_held:first_held + held]
        if held < n_experts:
            bound = window_rows(order.shape[0])
            windows = (jnp.sum(held_rows) + (bound - 1)) // bound
            return _held_move(
                (first_held, activation), windows, x, w_rows, matrices,
                order, inverse, group_sizes), \
                held_rows, windows * bound
        rows = _to_expert_order(x, order, inverse)
    with jax.named_scope("experts"):
        out = _through_experts(rows, w_rows, matrices, group_sizes, 0,
                               activation)
    with jax.named_scope("moe_dispatch"):
        return _combine(out, order, inverse), held_rows, None


def moe_mlp(h32, blk, *, experts_per_token: int, norm_topk_prob: bool, dtype,
            first_held: int = 0, scoring: str = "softmax", bias=None,
            scale: float = 1.0, activation=jax.nn.silu):
    """The layer.  h32: (B, S, D) float32, the block's normed input (the
    router reads it as it is, the experts its cast to ``dtype``); ``blk``
    holds ``router`` (D, E) and the matrices of the H experts from
    ``first_held`` on: ``w_gate``, ``w_up`` (H, D, F), ``w_down`` (H, F, D),
    or, experts without a gate, ``w_up`` and ``w_down`` alone
    (``down(activation(up(x)))``); H = E unless the chip holds a share,
    which ``w_up``'s leading size against the router's outputs says.
    ``scoring``, ``bias``, ``scale``: :func:`route`'s.  Where ``blk`` holds
    ``shared_up`` (D, F_s) and ``shared_down`` (F_s, D), a shared expert,
    ``shared_down(activation(shared_up(x)))`` of every token (with
    ``shared_gate`` (D, F_s) beside them, ``shared_down(activation(
    shared_gate(x)) * shared_up(x))``), is added to the routed sum after the
    combine; every chip of an expert-parallel job computes it alike.  -> (y
    (B, S, D) in ``dtype``, (load-balance, z), counts): the losses over all E experts; ``counts``
    what the data decided, by its name as a step counter
    (``tracing.STEP_COUNTER_REGISTRY``), each with one row for each shard of
    the batch (a chip's time follows that chip's rows), one in all without
    a mesh: ``moe_rows``, the pairs that reached each held expert, (shards,
    H) int32, and, where the layer holds a share, ``moe_moved``, the rows
    it moved, (shards,) int32."""
    batch_axes, _ = placement.axes(jax.sharding.get_abstract_mesh())
    names = ("w_gate", "w_up", "w_down") if "w_gate" in blk \
        else ("w_up", "w_down")
    share = blk["w_up"].shape[0] < blk["router"].shape[-1]

    def local(h32, router, *matrices):
        tokens = h32.reshape(-1, h32.shape[-1])
        with jax.named_scope("router"):
            weights, experts, losses = route(
                tokens, router, experts_per_token, norm_topk_prob, batch_axes,
                scoring, bias, scale)
        w_gate = matrices[0] if len(matrices) == 3 else None
        y, held_rows, moved = expert_mlp(
            tokens.astype(dtype), weights, experts, w_gate, *matrices[-2:],
            router.shape[-1], first_held, activation)
        counts = {step_counter("moe_rows"): held_rows[None]}
        if moved is not None:
            counts[step_counter("moe_moved")] = moved[None]
        return y.reshape(h32.shape), losses, counts

    # the rows cut, the router and the weights whole on every chip
    out = placement.place(
        local,
        (h32, blk["router"], *(blk[name].astype(dtype) for name in names)),
        ("r", "") + ("",) * len(names),
        ("r", ("", ""), dict.fromkeys(
            ("moe_rows", "moe_moved") if share else ("moe_rows",), "r")))
    if "shared_up" not in blk:
        return out
    y, losses, counts = out
    with jax.named_scope("shared_expert"):
        h = h32.astype(dtype)
        up = checkpoint_name(
            jnp.einsum("bsd,df->bsf", h, blk["shared_up"].astype(dtype)),
            remat.GATE_UP)
        if "shared_gate" in blk:
            gate = checkpoint_name(
                jnp.einsum("bsd,df->bsf", h,
                           blk["shared_gate"].astype(dtype)), remat.GATE_UP)
            act = (activation(gate.astype(jnp.float32))
                   * up.astype(jnp.float32)).astype(dtype)
        else:
            act = activation(up.astype(jnp.float32)).astype(dtype)
        shared = jnp.einsum("bsf,fd->bsd", act,
                            blk["shared_down"].astype(dtype))
        return _sum(y, shared), losses, counts
