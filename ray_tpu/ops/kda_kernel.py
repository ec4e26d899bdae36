"""The chunked gated delta rule of ``ops/kda.py`` as Pallas (Mosaic) kernels,
forward and backward: every decay, every scaled copy of q and k, the
products that make ``A`` and ``B``, ``U`` and the carried (d x d) state live
in VMEM.  HBM sees q, k, v and the cumulative sum ``G`` in, o out, their
cotangents, a chunk's three (chunk x chunk) matrices ``A``, ``B``, ``T`` (16
KB each a head) and, between the two kernels of a backward pass, the state
that comes into each chunk.

**Three stages** under one ``jax.custom_vjp`` (:func:`scan`):

1. :func:`ab_forward`: ``A`` (strictly lower, float32) and ``B`` (lower, q's
   dtype) from q, k and ``G``; nothing is carried, the grid (row, heads,
   chunk) is parallel.
2. The triangular system ``(I + diag(beta) A)^-1`` is left to XLA
   (``ops.kda._unit_lower_inverse``: 16 KB a chunk a head; in VMEM its
   dependent float32 products were no faster, PERF.md PR 45) with its
   backward written out, ``dN = -X^T dX X^T``; ``beta`` never enters a
   kernel.
3. :func:`outputs_forward`: the scan over a row's chunks, sequential, the
   state of the step's heads in VMEM scratch (transposed, value channels down
   the sublanes, so that the decay across a chunk scales lanes): ``U = T V -
   (T Kbar) S_0``, ``o = Qbar S_0 + B U``, ``S_C = Diag(e^{G_C}) S_0 + (k
   e^{G_C - G})^T U``.

The backward runs them the other way: :func:`outputs_backward` walks the
chunks last to first and carries the state's cotangent, XLA turns ``dT`` into
``dA``, and :func:`ab_backward` takes the first kernel's shares of dq, dk and
dG in float32 and adds its own, so that each is summed in float32 and
rounded once.

**``A`` and ``B`` as matmuls, every exponent a non-positive difference of
the one sum.**  The XLA form cuts a chunk into sub-chunks and writes the
decays inside a sub-chunk out, (16 x 16 x d) a sub-chunk.  Here the chunk is
halved again and again: at the level of width w the pairs (t, s) with t in
the second and s in the first half of one block of 2w positions take the
block's middle r (the second half's first position) as their reference,
``k_t e^{G_t - G_r}`` against ``k_s e^{G_r - G_s}``, both exponents
non-positive; one array ``e^{-|G - G_r|}`` a level serves both sides, and
the log2(chunk) levels' masks tile the strict lower triangle.  ``G_r`` over
a block is made by rolls along the positions and selects, its transpose in
the backward the same way: no sum over lanes anywhere but ``B``'s diagonal
``q_t . k_t``.  The decay being a channel's own, every cotangent of ``G`` is
elementwise in (position, channel); the two ends of a difference take their
shares of one float32 array.

**Numbers** are ``ops/kda.py``'s: ``G`` (formed once by XLA, a product with
a triangle of ones at full precision), the decays, ``A``, the inverse and
the state are float32; the products multiply in q's dtype and accumulate in
float32.  Where the XLA form keeps the pairs inside a sub-chunk of 16 in
float32, the levels under 16 multiply the scaled q and k split in two parts
of q's dtype, high and low (three products forward, two backward: the
cotangents' own low part moved no gradient's distance from the float32
recurrence): without the split the output is a tenth further from the
recurrence than the XLA form's and q's gradient a twentieth, with it none is
(``tests/test_kda_kernel.py``).  ``U`` is formed as the XLA form rounds it,
``T V - (T Kbar) S_0`` with ``T Kbar`` in q's dtype; q's and k's cotangents
are summed over both backward kernels in float32 and rounded once.

Not a TPU: Pallas' interpret mode (``ops/ssd_kernel.py`` does the same).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.kda import SUB, _unit_lower_inverse
from ray_tpu.ops.ssd_kernel import _F32, _NT, _TN, _dot, _interpret


def _rows(*parts):
    """Stacked down the sublanes."""
    return jnp.concatenate(parts, axis=0)


def _roll(x, shift: int):
    """``out[t] = x[t - shift]`` along the positions (the sublanes)."""
    return pltpu.roll(x, shift % x.shape[0], 0)


# ------------------------------------------------------- the levels' decays
def _levels(C: int):
    """Widths of the halves, widest first: C/2, ..., 2, 1."""
    return [C >> i for i in range(1, C.bit_length())]


class _Chunk:
    """What the ``A``/``B`` kernels form of a chunk's cumulative sum ``G``
    (C, d): for each level the decay ``e^{-|G - G_r|}`` to the middle r of
    the position's block of 2w, and the way back for its cotangent."""

    def __init__(self, G):
        C = G.shape[0]
        self.G, self.C = G, C
        self.pos = lax.broadcasted_iota(jnp.int32, G.shape, 0)
        # start[w][t] = G at the first position of t's block of w
        self.start, at, w = {1: G}, G, 1
        while w < C // 2:
            at = jnp.where(self.pos & w != 0, _roll(at, w), at)
            w *= 2
            self.start[w] = at

    def second(self, w: int):
        return self.pos & w != 0

    def decay(self, w: int):
        """(C, d): ``e^{G_t - G_r}`` in a block's second half, ``e^{G_r -
        G_s}`` in its first."""
        start, second = self.start[w], self.second(w)
        middle = jnp.where(second, start, _roll(start, -w))
        return jnp.exp(jnp.minimum(
            jnp.where(second, self.G - middle, middle - self.G), 0.0))

    def back(self, d_exponents):
        """{w: the cotangent of level w's exponent, (C, d)} -> dG: each
        position's own end, and the middles' ends summed back along the
        rolls that spread them."""
        direct, along = None, None
        for w in _levels(self.C):
            second = self.second(w)
            own = jnp.where(second, d_exponents[w], -d_exponents[w])
            direct = own if direct is None else direct + own
            # middle = where(second, start, roll(start, -w))
            d_start = jnp.where(second, -own, 0.0) + _roll(
                jnp.where(second, 0.0, -own), w)
            along = d_start if along is None else along + d_start
            if w > 1:  # start[w] = where(bit w/2, roll(start[w/2], w/2), ..)
                half = self.pos & (w // 2) != 0
                along = jnp.where(half, 0.0, along) + _roll(
                    jnp.where(half, along, 0.0), -(w // 2))
        return direct + along


def _pairs(C: int, w: int, copies: int = 1):
    """(copies x C, C) mask of the pairs [t, s] of level w, once for each
    stacked matrix: one block of 2w, t in its second half, s in its first."""
    t = lax.broadcasted_iota(jnp.int32, (copies * C, C), 0) & (C - 1)
    s = lax.broadcasted_iota(jnp.int32, (copies * C, C), 1)
    return ((t ^ s) & ~(w - 1) == w) & (t & w != 0)


def _split(x, dt):
    """x (float32) as a high and a low part in ``dt``; None for the low part
    where ``dt`` holds x whole."""
    high = x.astype(dt)
    if dt == _F32:
        return high, None
    return high, (x - high.astype(_F32)).astype(dt)


def _ab_kernel(q_ref, k_ref, g_ref, a_ref, b_ref, *, head_dim: int):
    dt = q_ref.dtype
    C = q_ref.shape[0]
    t = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    pairs = {w: _pairs(C, w, 2) for w in _levels(C)}
    for j in range(q_ref.shape[1] // head_dim):
        at = slice(j * head_dim, (j + 1) * head_dim)
        q, k = q_ref[:, at].astype(_F32), k_ref[:, at].astype(_F32)
        chunk = _Chunk(g_ref[:, at])
        both = jnp.zeros((2 * C, C), _F32)       # A over B
        for w in _levels(C):
            decay = chunk.decay(w)
            k_w, q_w = k * decay, q * decay
            high, low = _split(_rows(k_w, q_w), dt)
            got = _dot(high, high[:C], _NT)
            if w < SUB and low is not None:
                got += _dot(low, high[:C], _NT) + _dot(high, low[:C], _NT)
            both = jnp.where(pairs[w], got, both)
        a_ref[j] = both[:C]
        b_ref[j] = jnp.where(t == s, jnp.sum(q * k, axis=1, keepdims=True),
                             both[C:]).astype(dt)


def _ab_backward_kernel(q_ref, k_ref, g_ref, da_ref, db_ref, dq_in_ref,
                        dk_in_ref, dg_in_ref, dq_ref, dk_ref, dg_ref, *,
                        head_dim: int):
    """``dq_in``, ``dk_in``, ``dg_in`` (float32): what the scan's backward
    found for q, k and G; the sums are taken here, in float32, and q's and
    k's rounded once."""
    dt = q_ref.dtype
    C = q_ref.shape[0]
    t = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    pairs = {w: _pairs(C, w, 2) for w in _levels(C)}
    for j in range(q_ref.shape[1] // head_dim):
        at = slice(j * head_dim, (j + 1) * head_dim)
        q, k = q_ref[:, at].astype(_F32), k_ref[:, at].astype(_F32)
        chunk = _Chunk(g_ref[:, at])
        dB = db_ref[j].astype(_F32)
        both = _rows(da_ref[j], dB)
        on_diagonal = jnp.sum(jnp.where(t == s, dB, 0.0), axis=1,
                              keepdims=True)
        dq = dq_in_ref[:, at] + on_diagonal * k
        dk = dk_in_ref[:, at] + on_diagonal * q
        d_exponents = {}
        for w in _levels(C):
            decay = chunk.decay(w)
            k_w, q_w = k * decay, q * decay
            # A_w = k_w k_w^T, B_w = q_w k_w^T on the level's pairs
            cot = jnp.where(pairs[w], both, 0.0).astype(dt)
            scaled, low = _split(_rows(k_w, q_w), dt)
            left = _dot(cot, scaled[:C])             # dA k_w over dB k_w
            right = _dot(cot, scaled, _TN)           # dA^T k_w + dB^T q_w
            if w < SUB and low is not None:
                left += _dot(cot, low[:C])
                right += _dot(cot, low, _TN)
            dk_w = left[:C] + right
            dq_w = left[C:]
            dk += dk_w * decay
            dq += dq_w * decay
            d_exponents[w] = dk_w * k_w + dq_w * q_w
        dq_ref[:, at] = dq.astype(dt)
        dk_ref[:, at] = dk.astype(dt)
        dg_ref[:, at] = dg_in_ref[:, at] + chunk.back(d_exponents)


def _grid(x, chunk: int, heads: int, head_dim: int):
    """(rows, steps of ``heads`` heads, chunks)."""
    b, S, width = x.shape
    return b, width // (heads * head_dim), S // chunk


def _specs(chunk: int, heads: int, head_dim: int, at):
    """Block specs of a (b, S, H x d) array, of a chunk's (chunk x chunk)
    matrices (b, n, H, C, C) and of its incoming states (b, n, H, d, d);
    ``at`` maps the chunk axis' step to the chunk."""
    wide = pl.BlockSpec((None, chunk, heads * head_dim),
                        lambda i, h, c: (i, at(c), h))
    square = pl.BlockSpec((None, None, heads, chunk, chunk),
                          lambda i, h, c: (i, at(c), h, 0, 0))
    states = pl.BlockSpec((None, None, heads, head_dim, head_dim),
                          lambda i, h, c: (i, at(c), h, 0, 0))
    return wide, square, states


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _squares(x, chunk: int, head_dim: int, dtype):
    b, S, width = x.shape
    return jax.ShapeDtypeStruct(
        (b, S // chunk, width // head_dim, chunk, chunk), dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "head_dim"))
def ab_forward(q, k, G, chunk: int, heads: int, head_dim: int):
    """q, k: (b, S, H x d); G: (b, S, H x d) float32, the cumulative sum of
    the decay's log over each chunk.  -> (A (b, n, H, C, C) float32, zero on
    and above the diagonal; B the same in q's dtype, zero above it).
    Jitted, as the other three are: a model's layers then share one traced
    and lowered kernel a signature."""
    wide, square, _ = _specs(chunk, heads, head_dim, lambda c: c)
    return pl.pallas_call(
        functools.partial(_ab_kernel, head_dim=head_dim),
        grid=_grid(q, chunk, heads, head_dim),
        in_specs=[wide, wide, wide], out_specs=[square, square],
        out_shape=[_squares(q, chunk, head_dim, _F32),
                   _squares(q, chunk, head_dim, q.dtype)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=_interpret(), name="kda_ab_forward")(q, k, G)


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "head_dim"))
def ab_backward(q, k, G, dA, dB, dq, dk, dG, chunk: int, heads: int,
                head_dim: int):
    """-> (dq, dk in q's dtype, dG float32), the scan's shares ``dq``,
    ``dk``, ``dG`` (float32) added in."""
    wide, square, _ = _specs(chunk, heads, head_dim, lambda c: c)
    return pl.pallas_call(
        functools.partial(_ab_backward_kernel, head_dim=head_dim),
        grid=_grid(q, chunk, heads, head_dim),
        in_specs=[wide] * 3 + [square] * 2 + [wide] * 3,
        out_specs=[wide, wide, wide],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(G.shape, _F32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=_interpret(),
        name="kda_ab_backward")(q, k, G, dA, dB, dq, dk, dG)


# ---------------------------------------------------- the triangular system
def inverse_backward(X, dX):
    """X = (I + N)^-1 and its cotangent -> N's: ``-X^T dX X^T`` under the
    diagonal."""
    C = X.shape[-1]
    dN = -jnp.einsum("...ji,...jk,...lk->...il", X, dX, X,
                     precision=lax.Precision.HIGHEST)
    return jnp.where(np.tril(np.ones((C, C), bool), -1), dN, 0.0)


# ----------------------------------------- the states and the outputs: scan
class _Scaled:
    """A chunk's q and k scaled by the decays from the chunk's start
    (``bar``) and to its end (``k_end``), float32, a head."""

    def __init__(self, q_ref, k_ref, g_ref, at):
        G = g_ref[:, at]
        C = G.shape[0]
        q, k = q_ref[:, at].astype(_F32), k_ref[:, at].astype(_F32)
        self.grow = jnp.exp(G)                                  # e^{G_t}
        self.to_end = jnp.exp(jnp.minimum(G[C - 1:C] - G, 0.0))
        self.through = jnp.exp(G[C - 1:C])                      # 1, d
        self.k_bar, self.q_bar = k * self.grow, q * self.grow
        self.k_end = k * self.to_end

    def solved(self, T, v, state_low):
        """-> (U = T V - (T Kbar) S_0 and T Kbar in q's dtype, Qbar S_0
        float32), rounded where the XLA form rounds them."""
        dt, C, d = v.dtype, v.shape[0], v.shape[1]
        both = _dot(T, jnp.concatenate([v, self.k_bar.astype(dt)], axis=1))
        tk = both[:, d:].astype(dt)
        read = _dot(_rows(tk, self.q_bar.astype(dt)), state_low, _NT)
        return (both[:, :d] - read[:C]).astype(dt), tk, read[C:]


def _outputs_kernel(q_ref, k_ref, v_ref, g_ref, t_ref, b_ref, o_ref, *rest,
                    head_dim: int, keep_states: bool):
    if keep_states:
        incoming_ref, s_scr = rest
    else:
        (s_scr,) = rest

    @pl.when(pl.program_id(2) == 0)
    def _a_rows_first_chunk():
        s_scr[...] = jnp.zeros(s_scr.shape, s_scr.dtype)

    dt = q_ref.dtype
    C = q_ref.shape[0]
    for j in range(q_ref.shape[1] // head_dim):
        at = slice(j * head_dim, (j + 1) * head_dim)
        c = _Scaled(q_ref, k_ref, g_ref, at)
        state = s_scr[j]                      # (d value, d key), float32
        if keep_states:
            incoming_ref[j] = state
        u, _, read = c.solved(t_ref[j], v_ref[:, at], state.astype(dt))
        o_ref[:, at] = (read + _dot(b_ref[j], u)).astype(dt)
        s_scr[j] = state * c.through + _dot(u, c.k_end.astype(dt), _TN)


def _outputs_backward_kernel(q_ref, k_ref, v_ref, g_ref, t_ref, b_ref,
                             incoming_ref, do_ref,
                             dq_ref, dk_ref, dv_ref, dg_ref, dt_ref, db_ref,
                             ds_scr, *, head_dim: int):
    @pl.when(pl.program_id(2) == 0)  # the row's last chunk
    def _no_state_leaves_a_row():
        ds_scr[...] = jnp.zeros(ds_scr.shape, ds_scr.dtype)

    dt = q_ref.dtype
    C = q_ref.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (C, head_dim), 0)
    t = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    for j in range(q_ref.shape[1] // head_dim):
        at = slice(j * head_dim, (j + 1) * head_dim)
        c = _Scaled(q_ref, k_ref, g_ref, at)
        state, d_next = incoming_ref[j], ds_scr[j]
        state_low, d_next_low = state.astype(dt), d_next.astype(dt)
        T, B, dO, v = t_ref[j], b_ref[j], do_ref[:, at], v_ref[:, at]
        k_end = c.k_end.astype(dt)
        u, tk, _ = c.solved(T, v, state_low)    # the forward again
        # o = Qbar S_0 + B U;  S_C = through S_0 + U^T k_end
        dU = (_dot(B, dO, _TN) + _dot(k_end, d_next_low, _NT)).astype(dt)
        db_ref[j] = jnp.where(t >= s, _dot(dO, u, _NT), 0.0).astype(dt)
        d_k_end = _dot(u, d_next_low)
        # U = T V - (T Kbar) S_0 and Qbar S_0: what reached the state
        through = _rows(-dU, dO)
        reads = _dot(through, state_low)        # d(T Kbar) over dQbar
        ds_scr[j] = d_next * c.through + _dot(
            through, _rows(tk, c.q_bar.astype(dt)), _TN)
        solved = jnp.concatenate([dU, reads[:C].astype(dt)], axis=1)
        dt_ref[j] = _dot(solved, jnp.concatenate(
            [v, c.k_bar.astype(dt)], axis=1), _NT)
        under = _dot(T, solved, _TN)            # dV beside dKbar
        dv_ref[:, at] = under[:, :head_dim].astype(dt)
        d_k_bar, d_q_bar = under[:, head_dim:], reads[C:]
        dq_ref[:, at] = d_q_bar * c.grow
        dk_ref[:, at] = d_k_bar * c.grow + d_k_end * c.to_end
        # the decay is a channel's own: G's cotangent is elementwise, and
        # the chunk's last position takes what left through G_C
        to_end = d_k_end * c.k_end
        d_last = jnp.sum(to_end, axis=0, keepdims=True) + c.through * jnp.sum(
            d_next * state, axis=0, keepdims=True)
        dG = d_k_bar * c.k_bar + d_q_bar * c.q_bar - to_end
        dg_ref[:, at] = jnp.where(row == C - 1, dG + d_last, dG)


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "head_dim",
                                             "keep_states"))
def outputs_forward(q, k, v, G, T, B, chunk: int, heads: int, head_dim: int,
                     keep_states: bool):
    """-> (o (b, S, H x d), the state that came into each chunk (b, n, H, d
    value, d key) float32, or None)."""
    wide, square, states = _specs(chunk, heads, head_dim, lambda c: c)
    out_shape, out_specs = [jax.ShapeDtypeStruct(q.shape, q.dtype)], [wide]
    if keep_states:
        b, S, width = q.shape
        out_shape.append(jax.ShapeDtypeStruct(
            (b, S // chunk, width // head_dim, head_dim, head_dim), _F32))
        out_specs.append(states)
    out = pl.pallas_call(
        functools.partial(_outputs_kernel, head_dim=head_dim,
                          keep_states=keep_states),
        grid=_grid(q, chunk, heads, head_dim),
        in_specs=[wide] * 4 + [square] * 2,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, head_dim, head_dim), _F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret(), name="kda_scan_forward")(q, k, v, G, T, B)
    return out[0], (out[1] if keep_states else None)


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "head_dim"))
def outputs_backward(q, k, v, G, T, B, incoming, dO, chunk: int, heads: int,
                     head_dim: int):
    """-> (dq, dk, dG float32: the scan's shares, for :func:`ab_backward`;
    dv in q's dtype; dT float32; dB in q's dtype)."""
    n = q.shape[1] // chunk
    wide, square, states = _specs(chunk, heads, head_dim,
                                  lambda c: n - 1 - c)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_outputs_backward_kernel, head_dim=head_dim),
        grid=_grid(q, chunk, heads, head_dim),
        in_specs=[wide] * 4 + [square] * 2 + [states, wide],
        out_specs=[wide] * 4 + [square] * 2,
        out_shape=[like(G), like(G), like(v), like(G),
                   _squares(q, chunk, head_dim, _F32), like(B)],
        scratch_shapes=[pltpu.VMEM((heads, head_dim, head_dim), _F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="kda_scan_backward")(q, k, v, G, T, B, incoming, dO)


# ------------------------------------------------------------ the operation
def heads_a_step(heads: int) -> int:
    """Heads a grid step takes: all of a chip's where they are few (a step
    costs what it costs whatever it holds, and the heads' chains of small
    products fill each other's waits), eight at most."""
    return max(j for j in range(1, min(heads, 8) + 1) if heads % j == 0)


def grid(q, chunk: int):
    """(rows, steps of heads, chunks): the extents the kernels walk."""
    b, S, H, _ = q.shape
    return b, H // heads_a_step(H), S // chunk


def within_chunks(g, chunk: int):
    """The cumulative sum of g (b, S, width) over each chunk by itself, the
    position included: a product with a triangle of ones at full float32
    precision, as ``kda_xla`` forms it."""
    b, S, width = g.shape
    upto = np.tril(np.ones((chunk, chunk), np.float32))   # [t, s]: s <= t
    return jnp.einsum("ts,bnsw->bntw", upto,
                      g.reshape(b, S // chunk, chunk, width),
                      precision=lax.Precision.HIGHEST).reshape(g.shape)


def _forward(q, k, v, g, beta, chunk, heads, inverse, keep_states):
    b, S, H, d = q.shape
    heads = heads or heads_a_step(H)
    q, k, v = (a.reshape(b, S, H * d) for a in (q, k, v))
    G = within_chunks(g.astype(_F32).reshape(b, S, H * d), chunk)
    A, B = ab_forward(q, k, G, chunk, heads, d)
    # beta a chunk and a head, (b, n, H, C): it scales N's rows and T's
    # columns and never enters a kernel
    beta = jnp.moveaxis(beta.astype(_F32).reshape(b, S // chunk, chunk, H),
                        3, 2)
    X = inverse(A * beta[..., None])
    T = (X * beta[..., None, :]).astype(q.dtype)
    o, incoming = outputs_forward(q, k, v, G, T, B, chunk, heads, d,
                                  keep_states)
    return o.reshape(b, S, H, d), (q, k, v, G, beta, A, X, T, B, incoming)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def scan(q, k, v, g, beta, chunk: int, heads: int | None = None,
         keep_states: bool = True, inverse=_unit_lower_inverse):
    """``ops.kda.kda``'s arguments and result, by the kernels.  ``heads`` a
    grid step (None: :func:`heads_a_step`), ``keep_states`` (the forward of
    a differentiated call writes each chunk's incoming state for the
    backward; else the backward runs the forward kernel once more for them)
    and the ``inverse`` of the unit-triangular system are what
    ``scripts/kda_kernel_sweep.py`` varies."""
    return _forward(q, k, v, g, beta, chunk, heads, inverse, False)[0]


def _scan_fwd(q, k, v, g, beta, chunk, heads, keep_states, inverse):
    o, saved = _forward(q, k, v, g, beta, chunk, heads, inverse, keep_states)
    # of g and beta their cotangents' types alone
    return o, (saved, jnp.zeros((), g.dtype), jnp.zeros((), beta.dtype))


def _scan_bwd(chunk, heads, keep_states, inverse, saved, dO):
    # traced under the name stack of the call it is the backward of: the
    # caller's ``kda_scan`` scope names these calls too
    (q, k, v, G, beta, A, X, T, B, incoming), like_g, like_beta = saved
    b, S, H, d = dO.shape
    heads = heads or heads_a_step(H)
    if incoming is None:
        incoming = outputs_forward(q, k, v, G, T, B, chunk, heads, d, True)[1]
    dq, dk, dv, dG, dT, dB = outputs_backward(
        q, k, v, G, T, B, incoming, dO.reshape(q.shape), chunk, heads, d)
    # T = X diag(beta), X = (I + diag(beta) A)^-1
    dN = inverse_backward(X, dT * beta[..., None, :])
    d_beta = jnp.sum(dT * X, axis=-2) + jnp.sum(dN * A, axis=-1)
    dq, dk, dG = ab_backward(q, k, G, dN * beta[..., None], dB, dq, dk, dG,
                             chunk, heads, d)
    # the sum's cotangent summed back from each chunk's end
    after = np.triu(np.ones((chunk, chunk), np.float32))   # [t, s]: s >= t
    dg = jnp.einsum("ts,bnsw->bntw", after,
                    dG.reshape(b, S // chunk, chunk, H * d),
                    precision=lax.Precision.HIGHEST)
    d_beta = jnp.moveaxis(d_beta, 2, 3).reshape(b, S, H)
    heads_apart = (b, S, H, d)
    return (dq.reshape(heads_apart), dk.reshape(heads_apart),
            dv.reshape(heads_apart),
            dg.reshape(heads_apart).astype(like_g.dtype),
            d_beta.astype(like_beta.dtype))


scan.defvjp(_scan_fwd, _scan_bwd)
