"""The chunked state-space scan of ``ops/ssd.py`` as two Pallas (Mosaic)
kernels, one forward and one backward: everything that is (chunk x chunk) or
(head_dim x state) lives in VMEM.  HBM sees the scan's inputs, its output,
their cotangents and, between the two kernels of a backward pass, the state
that comes into each chunk.

**The grid** is (row, group of heads, chunk) with the chunk axis sequential:
the forward walks a row's chunks first to last and carries the state of the
group's J heads in VMEM scratch, zeroed at a row's first chunk; the backward
walks them last to first and carries the state's cotangent the same way.  A
grid step reads one chunk of x as (Q, J x P), of B and C as (Q, N), of
``delta`` as (Q, J) and of the cumulative sum of ``delta A`` over the chunk
(float32, formed by XLA before the call: a few MB) in two layouts, positions
down the sublanes (Q, J) and positions along the lanes (J, Q): a decay
``exp(cum_t - cum_s)`` needs ``cum_t`` down one axis and ``cum_s`` along the
other, and the chip has no cheap way to turn an (8, 128) tile.

**Lanes.**  A head of P < 128 channels is a part of a lane tile, so the
kernels never slice a head out: they work on tiles of 128 lanes (128 / P
heads side by side; two at P = 64) and zero the other heads' lanes of one
operand where a product is a head's own (``(L o C B^T) (delta x)`` and its
transposes: the matrix unit is 128 wide either way).  The carried state is
kept transposed, (N, 128) a lane tile, so that ``C h^T``, the chunk's state
``B^T (w x)`` and their cotangents are each one product a tile for all its
heads.

**Numbers** are ``ops/ssd.py``'s: the cumulative sum of ``delta A`` over the
chunk in float32 at full precision (a product with a triangle of ones),
every decay ``exp`` of a difference of that one sum, masked and held at or
under zero; the products multiply in x's dtype and accumulate in
float32.  Where the XLA form rounds ``decay x scores`` and ``delta x`` to x's
dtype, the kernels round their product ``delta_s x decay x scores`` once and
multiply it with x as it came (one rounding fewer, and what makes the
backward's sums below come out of one array); ``delta x decay-to-the-end x``
and the incoming state are rounded as the XLA form rounds them; the carried
state and its cotangent are float32.

**The backward** re-forms a chunk's decays and scores (transposed: row s,
column t) and never transposes a (Q, Q) array a head.  The cotangent of the
difference ``cum_t - cum_s`` is one (Q, Q) array a head, ``d(mixed) o
mixed`` with ``mixed`` as it was multiplied, and both ends take their sums
of that same array: over a chunk the two nearly cancel, and sums of two
roundings of it would not.  Its column sums are taken as such; its row sums
are ``x_s . G_s``, G the cotangent of x through the same product, which is
also ``delta_s`` times what d delta wants directly: one sum over a head's
lanes serves both (sums over lanes are what this kernel waits for; the
ablation is in PERF.md, PR 44).  It writes dx, dB and dC (summed over the group's heads in
the kernel), the direct part of d delta, the sum's cotangent in each layout
and (8, J x P) partial sums of ``dy x`` a (row, group) for dD; a few small
XLA operations sum the sum's cotangent back over each chunk and finish
d delta, dA and dD.

Not a TPU: Pallas' interpret mode.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))  # a b^T
_TN = (((0,), (0,)), ((), ()))  # a^T b


def tiles(chunk: int, heads_per_group: int, head_dim: int, state: int) -> bool:
    """Whether a scan of these sizes lies on the chip's tiles: chunks and
    states of whole lane tiles, heads that fill lane tiles side by side."""
    return (chunk % LANES == 0 and state % LANES == 0
            and LANES % head_dim == 0
            and heads_per_group % (LANES // head_dim) == 0)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _triangles(Q: int):
    """(lower, upper) triangles of ones, diagonal included: ``lower[t, s]``
    is s <= t."""
    row = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return row >= col, row <= col


class _Chunk:
    """What both kernels form of a chunk's ``delta`` and of the cumulative
    sum of its ``delta A`` (both layouts, from :func:`_layouts`): the decays
    from the chunk's start (``grow``), to its end (``to_end``; ``left``
    with ``delta``) and across it (``carry``), a head a column, and how a
    head's column spreads over its lanes."""

    def __init__(self, cum_row_ref, cum_col_ref, delta_row_ref, delta_ref,
                 head_dim: int):
        Q = delta_ref.shape[0]
        self.lower, self.upper = _triangles(Q)
        self.cum_row, self.cum_col = cum_row_ref[...], cum_col_ref[...]
        total = self.cum_col[Q - 1:Q, :]                               # 1, J
        self.delta_row, self.delta = delta_row_ref[...], delta_ref[...]
        self.grow = jnp.exp(self.cum_col)
        self.to_end = jnp.exp(total - self.cum_col)
        self.left = self.delta * self.to_end  # what x_s adds to the state
        self.carry = jnp.exp(total)
        self.per_tile = LANES // head_dim
        self.head_of_lane = lax.broadcasted_iota(
            jnp.int32, (1, LANES), 1) // head_dim

    def heads(self, tile: int):
        """(place in the tile, head of the group) of a lane tile's heads."""
        return [(q, tile * self.per_tile + q) for q in range(self.per_tile)]

    def spread(self, columns, tile: int):
        """(rows, J) -> (rows, 128): each head of the tile over its lanes."""
        out = None
        for q, j in self.heads(tile):
            mine = columns[:, j:j + 1]
            out = mine if out is None else jnp.where(
                self.head_of_lane == q, mine, out)
        return jnp.broadcast_to(out, (columns.shape[0], LANES))

    def own(self, values, q: int):
        """``values`` (rows, 128) with the other heads' lanes zeroed."""
        if self.per_tile == 1:
            return values
        return jnp.where(self.head_of_lane == q, values, 0.0)

    def fed_decay(self, j: int, transposed: bool):
        """Head j's (Q, Q) weights of ``x_s`` in ``y_t`` but for the scores,
        [t, s] or ``transposed`` [s, t]: ``delta_s exp(cum_t - cum_s)``
        where s <= t, zero elsewhere."""
        col, row = self.cum_col[:, j:j + 1], self.cum_row[j:j + 1, :]
        if transposed:
            between, keep, delta = row - col, self.upper, self.delta[:, j:j + 1]
        else:
            between, keep = col - row, self.lower
            delta = self.delta_row[j:j + 1, :]
        return jnp.where(keep, jnp.exp(jnp.minimum(between, 0.0)) * delta,
                         0.0)


# ----------------------------------------------------------------- forward
def _forward_kernel(x_ref, b_ref, c_ref, cum_row_ref, cum_col_ref,
                    delta_row_ref, delta_ref, d_ref, y_ref, *rest,
                    head_dim: int, keep_states: bool):
    if keep_states:
        incoming_ref, h_scr = rest
    else:
        (h_scr,) = rest

    @pl.when(pl.program_id(2) == 0)
    def _a_rows_first_chunk():
        h_scr[...] = jnp.zeros(h_scr.shape, h_scr.dtype)

    dt = x_ref.dtype
    Bm, Cm = b_ref[...], c_ref[...]
    chunk = _Chunk(cum_row_ref, cum_col_ref, delta_row_ref, delta_ref,
                   head_dim)
    scores = _dot(Cm, Bm, _NT)                                   # [t, s]
    for tile in range(x_ref.shape[1] // LANES):
        at = slice(tile * LANES, (tile + 1) * LANES)
        x = x_ref[:, at].astype(_F32)
        y = x * d_ref[:, at]
        for q, j in chunk.heads(tile):
            mixed = (chunk.fed_decay(j, False) * scores).astype(dt)
            y += _dot(mixed, chunk.own(x, q).astype(dt))
        h = h_scr[tile]                                          # N, 128
        if keep_states:
            incoming_ref[tile] = h
        y += _dot(Cm, h.astype(dt)) * chunk.spread(chunk.grow, tile)
        y_ref[:, at] = y.astype(dt)
        left = x * chunk.spread(chunk.left, tile)
        h_scr[tile] = h * chunk.spread(chunk.carry, tile) \
            + _dot(Bm, left.astype(dt), _TN)


def _within_chunks(values, chunk: int, reverse: bool = False):
    """The cumulative sum of ``values`` (..., S) over each chunk of
    positions by itself (``reverse``: from the chunk's end), the position
    included: a product with a triangle of ones at full float32 precision,
    as ``ssd_xla`` forms it (``jnp.cumsum`` lowers to a ``reduce_window``)."""
    at = np.arange(chunk)
    ones = (at[:, None] >= at[None, :]) if reverse \
        else (at[:, None] <= at[None, :])
    split = values.reshape(*values.shape[:-1], -1, chunk)
    return jnp.einsum("...s,st->...t", split, ones.astype(np.float32),
                      precision=lax.Precision.HIGHEST).reshape(values.shape)


class _LaidOut(NamedTuple):
    """The scan's inputs as the kernels' blocks cut them, in the order the
    kernels take them: x (b, S, H x P), B and C (b, S, G x N) with heads
    and groups folded into the lanes; the cumulative sum of a group's
    ``delta A`` over each chunk, float32 and formed once, a head a row, (b,
    G, J, S), and a head a column, its transpose: a decay's two ends are
    then numbers of one sum, bit for bit, and the diagonal is ``exp(0)``;
    ``delta`` in the same two layouts; D a lane, (1, H x P).  (The sum is a
    few MB and XLA's to form: as products of (8 x 128) with a triangle
    inside the kernels it took the matrix unit as long as a chunk's real
    products.)"""

    x: Any
    B: Any
    C: Any
    cum_row: Any
    cum_col: Any
    delta_row: Any
    delta_col: Any
    d_lanes: Any

    @property
    def sizes(self):
        """(rows, positions, groups, heads a group, lanes a group, state)."""
        (b, S, width), (G, J) = self.x.shape, self.cum_row.shape[1:3]
        return b, S, G, J, width // G, self.B.shape[2] // G


def _layouts(x, delta, A, B, C, D, chunk: int) -> _LaidOut:
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    J = H // G
    delta_row = jnp.moveaxis(delta.astype(_F32).reshape(b, S, G, J), 1, 3)
    cum_row = _within_chunks(delta_row * A.astype(_F32).reshape(G, J, 1),
                             chunk)
    return _LaidOut(
        x.reshape(b, S, H * P), B.reshape(b, S, G * N),
        C.reshape(b, S, G * N), cum_row, jnp.swapaxes(cum_row, 2, 3),
        delta_row, jnp.swapaxes(delta_row, 2, 3),
        jnp.repeat(D.astype(_F32), P).reshape(1, H * P))


def _specs(width: int, J: int, N: int, Q: int, at):
    """Block specs of x (``width`` = J x P lanes a group), B and C, the
    small arrays a head a column and a head a row, D, and the boundary
    states; ``at`` maps the chunk axis' step to the chunk (the backward
    walks it from the end)."""
    wide = pl.BlockSpec((None, Q, width), lambda i, g, k: (i, at(k), g))
    group = pl.BlockSpec((None, Q, N), lambda i, g, k: (i, at(k), g))
    col = pl.BlockSpec((None, None, Q, J), lambda i, g, k: (i, g, at(k), 0))
    row = pl.BlockSpec((None, None, J, Q), lambda i, g, k: (i, g, 0, at(k)))
    lane = pl.BlockSpec((1, width), lambda i, g, k: (0, g))
    states = pl.BlockSpec((None, None, None, width // LANES, N, LANES),
                          lambda i, g, k: (i, at(k), g, 0, 0, 0))
    return wide, group, col, row, lane, states


def grid(x, B, chunk: int):
    """(rows, groups of heads, chunks): the extents both kernels walk."""
    return x.shape[0], B.shape[2], x.shape[1] // chunk


_SEQUENTIAL_CHUNKS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("chunk", "keep_states"))
def forward(laid_out: _LaidOut, chunk: int, keep_states: bool):
    """The forward kernel over :func:`_layouts`' arrays -> (y as (b, S, H x
    P), the state that came into each chunk as (b, c, G, J x P / 128, N,
    128) float32, or None).  Jitted, as :func:`backward` is: a model's
    layers then share one traced and lowered kernel a signature."""
    x = laid_out.x
    b, S, G, J, width, N = laid_out.sizes
    c = S // chunk
    wide, group, col, row, lane, states = _specs(width, J, N, chunk,
                                                 lambda k: k)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [wide]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, c, G, width // LANES, N, LANES), _F32))
        out_specs.append(states)
    out = pl.pallas_call(
        functools.partial(_forward_kernel, head_dim=width // J,
                          keep_states=keep_states),
        grid=(b, G, c),
        in_specs=[wide, group, group, row, col, row, col, lane],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((width // LANES, N, LANES), _F32)],
        compiler_params=_SEQUENTIAL_CHUNKS, interpret=_interpret(),
        name="ssd_forward",
    )(*laid_out)
    return out[0], (out[1] if keep_states else None)


# ---------------------------------------------------------------- backward
def _backward_kernel(x_ref, b_ref, c_ref, cum_row_ref, cum_col_ref,
                     delta_row_ref, delta_ref, d_ref, incoming_ref, dy_ref,
                     dx_ref, db_ref, dc_ref, dcum_col_ref, dcum_row_ref,
                     ddelta_ref, dd_ref,
                     dh_scr, met_scr, fed_scr, grown_scr, *, head_dim: int):
    """``met_scr``, ``fed_scr``, ``grown_scr`` (Q, J): a head a column, the
    sums over its channels of ``x U`` (U the cotangent of the rows that fed
    the chunk's state), of ``x G`` (G the cotangent of x through the
    chunk's own product) and of ``dy (C h^T)``."""
    @pl.when(pl.program_id(2) == 0)  # the row's last chunk
    def _no_state_leaves_a_row():
        dh_scr[...] = jnp.zeros(dh_scr.shape, dh_scr.dtype)
        dd_ref[...] = jnp.zeros(dd_ref.shape, dd_ref.dtype)

    dt = x_ref.dtype
    Q, J = delta_ref.shape
    Bm, Cm = b_ref[...], c_ref[...]
    chunk = _Chunk(cum_row_ref, cum_col_ref, delta_row_ref, delta_ref,
                   head_dim)
    scores = _dot(Bm, Cm, _NT)                                   # [s, t]
    d_scores = jnp.zeros((Q, Q), _F32)
    dB = jnp.zeros(Bm.shape, _F32)
    dC = jnp.zeros(Cm.shape, _F32)
    held = jnp.zeros((1, J), _F32)  # <dh', h> a head
    head_column = lax.broadcasted_iota(jnp.int32, (1, J), 1)
    for tile in range(x_ref.shape[1] // LANES):
        at = slice(tile * LANES, (tile + 1) * LANES)
        x = x_ref[:, at].astype(_F32)
        dy_low = dy_ref[:, at]
        dy = dy_low.astype(_F32)
        h, dh_out = incoming_ref[tile], dh_scr[tile]             # N, 128
        h_low, dh_low = h.astype(dt), dh_out.astype(dt)
        # across chunks: y += grow (C h^T);  h' = carry h + B^T (left x)
        to_end = chunk.spread(chunk.left, tile)
        reached = _dot(Cm, h_low)                                # Q, 128
        dz = (dy * chunk.spread(chunk.grow, tile)).astype(dt)
        left = (x * to_end).astype(dt)
        U = _dot(Bm, dh_low)                                     # Q, 128
        dC += _dot(dz, h_low, _NT)
        dB += _dot(left, dh_low, _NT)
        dh_scr[tile] = dh_out * chunk.spread(chunk.carry, tile) \
            + _dot(Cm, dz, _TN)
        # inside the chunk, a head at a time: y_t += sum_s mixed[s, t] x_s
        G = jnp.zeros((Q, LANES), _F32)
        for q, j in chunk.heads(tile):
            fed_decay = chunk.fed_decay(j, True)
            mixed = (fed_decay * scores).astype(dt)
            G += _dot(mixed, chunk.own(dy, q).astype(dt))
            d_mixed = _dot(chunk.own(x, q).astype(dt), dy_low, _NT)
            d_scores += d_mixed * fed_decay
            # d(cum_t - cum_s) of the product as it was multiplied: its
            # column sums here, its row sums ``x_s . G_s`` below
            dcum_row_ref[j:j + 1, :] = jnp.sum(
                d_mixed * mixed.astype(_F32), axis=0, keepdims=True)
        dx_ref[:, at] = (G + to_end * U + d_ref[:, at] * dy).astype(dt)
        dd_ref[:, at] += (dy * x).reshape(Q // 8, 8, LANES).sum(axis=0)
        # a head's sums over its own lanes
        inner = jnp.sum(dh_out * h, axis=0, keepdims=True)       # 1, 128
        for scr, values in ((met_scr, x * U), (fed_scr, x * G),
                            (grown_scr, dy * reached)):
            for q, j in chunk.heads(tile):
                scr[:, j:j + 1] = jnp.sum(chunk.own(values, q), axis=1,
                                          keepdims=True)
        for q, j in chunk.heads(tile):
            held = jnp.where(
                head_column == j,
                jnp.sum(chunk.own(inner, q), axis=1, keepdims=True), held)
    d_scores = d_scores.astype(dt)
    db_ref[...] = (dB + _dot(d_scores, Cm)).astype(dt)
    dc_ref[...] = (dC + _dot(d_scores, Bm, _TN)).astype(dt)
    # d delta directly, and d cum a column: the row sums above, what the
    # chunk's state and the incoming one bring, the total at the last row
    met, fed_sum, grown = met_scr[...], fed_scr[...], grown_scr[...]
    left_sum = chunk.left * met
    # x_s . G_s is delta_s times the sum d delta_s wants; a delta that
    # underflowed to zero has no gradient to pass on through its softplus
    ddelta_ref[...] = fed_sum / jnp.maximum(chunk.delta, 1e-30) \
        + chunk.to_end * met
    d_total = chunk.carry * held + jnp.sum(left_sum, axis=0, keepdims=True)
    last = lax.broadcasted_iota(jnp.int32, (Q, J), 0) == Q - 1
    dcum_col_ref[...] = chunk.grow * grown - left_sum - fed_sum \
        + jnp.where(last, d_total, 0.0)


@functools.partial(jax.jit, static_argnames=("chunk",))
def backward(laid_out: _LaidOut, A, incoming, dy, chunk: int):
    """The backward kernel over :func:`_layouts`' arrays, the boundary
    states and ``dy`` as (b, S, H x P) -> the cotangents of x (b, S, H x P),
    delta (b, S, H), A (H,), B and C (b, S, G x N) and D (H,)."""
    x, B, C = laid_out[:3]
    b, S, G, J, width, N = laid_out.sizes
    c = S // chunk
    wide, group, col, row, lane, states = _specs(width, J, N, chunk,
                                                 lambda k: c - 1 - k)
    partial = pl.BlockSpec((None, None, 8, width), lambda i, g, k: (i, g, 0, 0))
    small = jax.ShapeDtypeStruct((b, G, S, J), _F32)
    dx, dB, dC, dcum_col, dcum_row, ddelta, dD = pl.pallas_call(
        functools.partial(_backward_kernel, head_dim=width // J),
        grid=(b, G, c),
        in_specs=[wide, group, group, row, col, row, col, lane, states, wide],
        out_specs=[wide, group, group, col, row, col, partial],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(B.shape, B.dtype),
                   jax.ShapeDtypeStruct(C.shape, C.dtype),
                   small, jax.ShapeDtypeStruct((b, G, J, S), _F32), small,
                   jax.ShapeDtypeStruct((b, G, 8, width), _F32)],
        scratch_shapes=[pltpu.VMEM((width // LANES, N, LANES), _F32)]
        + [pltpu.VMEM((chunk, J), _F32)] * 3,
        compiler_params=_SEQUENTIAL_CHUNKS, interpret=_interpret(),
        name="ssd_backward",
    )(*laid_out, incoming, dy)
    # d(delta A): the sums' cotangent summed back from each chunk's end
    da = _within_chunks(jnp.swapaxes(dcum_col, 2, 3) + dcum_row, chunk,
                        reverse=True)                            # b, G, J, S
    d_delta = jnp.swapaxes(ddelta, 2, 3) + da * A.astype(_F32).reshape(G, J, 1)
    d_delta = jnp.moveaxis(d_delta, 3, 1).reshape(b, S, G * J)
    dA = jnp.sum(da * laid_out.delta_row, axis=(0, 3)).reshape(G * J)
    dD = jnp.sum(dD.reshape(b, G, 8, J, width // J), axis=(0, 2, 4))
    return dx, d_delta, dA, dB, dC, dD.reshape(G * J)


# ------------------------------------------------------------ the operation
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def scan(x, delta, A, B, C, D, chunk: int, keep_states: bool = True):
    """``ops.ssd.ssd``'s arguments and result, by the two kernels.
    ``keep_states``: the forward of a differentiated call writes each chunk's
    incoming state for the backward (else the backward runs the forward
    kernel once more for them; what ``scripts/ssd_kernel_sweep.py``
    compares)."""
    y, _ = forward(_layouts(x, delta, A, B, C, D, chunk), chunk, False)
    return y.reshape(x.shape)


def _scan_fwd(x, delta, A, B, C, D, chunk, keep_states):
    laid_out = _layouts(x, delta, A, B, C, D, chunk)
    y, incoming = forward(laid_out, chunk, keep_states)
    # the inputs as the kernels read them (x, B and C are the caller's
    # arrays seen flat; the sums and delta's two layouts a few MB) and the
    # boundary states; delta, A, D for their cotangents' types
    return y.reshape(x.shape), (laid_out, incoming, delta, A, D)


def _scan_bwd(chunk, keep_states, saved, dy):
    # traced under the name stack of the call it is the backward of: the
    # caller's ``ssm_scan`` scope names these calls too
    laid_out, incoming, delta, A, D = saved
    if incoming is None:
        incoming = forward(laid_out, chunk, True)[1]
    dx, d_delta, dA, dB, dC, dD = backward(
        laid_out, A, incoming, dy.reshape(laid_out.x.shape), chunk)
    b, S, G = laid_out.sizes[:3]
    groups = (b, S, G, -1)
    return (dx.reshape(dy.shape), d_delta.astype(delta.dtype),
            dA.astype(A.dtype), dB.reshape(groups), dC.reshape(groups),
            dD.astype(D.dtype))


scan.defvjp(_scan_fwd, _scan_bwd)
