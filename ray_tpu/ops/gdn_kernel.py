"""The chunked gated delta rule of ``ops/gdn.py`` as Pallas (Mosaic) kernels,
forward and backward: ``Gamma``, ``B``, every scaled copy of q and k
(``Kbar``, ``Qbar``, ``K e^{G_C - G}``, ``T Kbar``), ``U`` and the carried
(dk x dv) state live in VMEM.  HBM sees q, k, v and the cumulative sum ``G``
in, o out, their cotangents, a chunk's ``A`` and ``T`` (and their
cotangents; 16 KB each a head at chunks of 64) and the state that comes into
each chunk, which the forward writes for the backward.

**Three stages** under one ``jax.custom_vjp`` (:func:`scan`):

1. :func:`a_forward`: ``A = tril(K K^T, -1) * Gamma`` (float32) from k and
   ``G``; nothing is carried, the grid (row, heads, chunk) is parallel.
2. The triangular system ``(I + diag(beta) A)^-1`` is left to XLA
   (``ops.kda._unit_lower_inverse``, as ``ops/kda_kernel.py`` leaves it)
   with its backward written out, ``dN = -X^T dX X^T``; ``beta`` never
   enters a kernel.
3. :func:`outputs_forward`: the scan over a row's chunks, sequential, the
   float32 (dk x dv) states of the step's heads in VMEM scratch: ``B =
   tril(Q K^T) * Gamma`` (one product and one (C x C) ``exp``: cheaper made
   again than read), ``U = T V - (T Kbar) S_0``, ``o = Qbar S_0 + B U``,
   ``S_C = e^{G_C} S_0 + (K e^{G_C - G})^T U``; it writes the state that
   comes into each chunk, which the backward reads.

The backward runs them the other way: :func:`outputs_backward` walks the
chunks last to first, carries the state's cotangent and finishes dq and dv;
XLA turns ``dT`` into ``dA``; :func:`a_backward` takes the scan's share of
dk in float32 and adds ``A``'s, so that dk is summed in float32 and rounded
once.  The decay being one number a position and a head, ``G``'s cotangent
is small: each kernel writes its share in the two layouts below and XLA adds
the four.

**Lanes.**  Keys of 96 and values of 192 fill no lane tile.  The kernels
take q, k and v *as the projections wrote them*, (rows, positions, H x d),
a grid step all H heads' columns of a chunk, and slice a head out at the
lane offset ``j d`` (Mosaic shifts the lanes of the three heads in four that
start inside a tile): no heads-major copy is made on either side of a call
and no padding reaches HBM.  ``scripts/gdn_scan_sweep.py`` times this
against keys padded to 128; heads-major operands, turned by XLA, lost that
sweep and their arm left the kernels with PR 60 (PERF.md, PR 59).  Where
``heads x dk`` and ``heads x dv`` are whole lane tiles for a divisor of H, a
grid step may take that many heads (:func:`heads_a_step`).

**``G`` in two layouts**, formed once by XLA (a product with a triangle of
ones at full float32 precision): positions down the sublanes, (C, heads),
for what scales a position's row (``e^{G_t}``, ``e^{G_C - G_t}``), and
positions along the lanes, (heads, C), for ``Gamma``'s columns: ``Gamma_ts =
exp(G_t - G_s)`` needs ``G_t`` down one axis and ``G_s`` along the other,
and the chip has no cheap way to turn a column (``ops/ssd_kernel.py`` does
the same).  Every exponent is a non-positive difference, masked before the
``exp``.

**Numbers** are ``ops/gdn.py``'s: ``G``, the decays, ``A``, the inverse and
the state are float32; the products multiply in q's dtype and accumulate in
float32; ``T``, ``T Kbar``, ``U``, ``B`` and the entering state are rounded
to q's dtype where the XLA form rounds them.

Not a TPU: Pallas' interpret mode (``ops/ssd_kernel.py`` does the same).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import remat
from ray_tpu.ops.kda import _unit_lower_inverse
from ray_tpu.ops.kda_kernel import _rows, inverse_backward, within_chunks
from ray_tpu.ops.ssd_kernel import LANES, _F32, _NT, _TN, _dot, _interpret


def _head(ref, j: int, d: int):
    """Head j's (C, d) of a block: its columns of a (C, heads x d) block of
    the projections' layout."""
    return ref.at[:, j * d:(j + 1) * d]


class _Chunk:
    """What the kernels form of a head's cumulative sum ``G`` over a chunk,
    from its two layouts (``down`` (C, 1), ``along`` (1, C)): ``Gamma`` with
    the triangle's masks, and the decays from the chunk's start (``grow``),
    to its end (``to_end``) and across it (``through``)."""

    def __init__(self, down, along):
        C = self.C = down.shape[0]
        t = lax.broadcasted_iota(jnp.int32, (C, C), 0)
        s = lax.broadcasted_iota(jnp.int32, (C, C), 1)
        self.lower, self.strict = s <= t, s < t
        self.gamma = jnp.exp(jnp.where(self.lower, down - along, -jnp.inf))
        self.down = down

    def scales(self, dv: int):
        C, down = self.C, self.down
        self.grow = jnp.exp(down)                              # e^{G_t}
        self.to_end = jnp.exp(jnp.minimum(down[C - 1:C] - down, 0.0))
        # e^{G_C} along a state's lanes: Mosaic spreads a number over
        # sublanes or over lanes, not both at once
        self.through = jnp.exp(jnp.broadcast_to(down[C - 1:C], (1, dv)))
        return self


def _sums(pairs):
    """The cotangent of ``G_t - G_s`` over a chunk's pairs, (C, C) -> its
    share of ``G``'s in each layout: the rows' sums (C, 1) for the t end,
    minus the columns' (1, C) for the s end, both of the one array."""
    return (jnp.sum(pairs, axis=1, keepdims=True),
            -jnp.sum(pairs, axis=0, keepdims=True))


class _Columns:
    """``G``'s two layouts of a grid step's heads, read a head at a time, and
    its cotangent's, gathered a head at a time and written once."""

    def __init__(self, g_col_ref, g_row_ref, *out_refs):
        self.col, self.row, self.out = g_col_ref, g_row_ref, out_refs
        if out_refs:
            self.d_col = jnp.zeros(g_col_ref.shape, _F32)
            self.d_row = jnp.zeros(g_row_ref.shape, _F32)
            self.lane = lax.broadcasted_iota(jnp.int32, g_col_ref.shape, 1)
            self.line = lax.broadcasted_iota(jnp.int32, g_row_ref.shape, 0)

    def of(self, j: int):
        return self.col[:, j:j + 1], self.row[j:j + 1, :]

    def put(self, j: int, down, along):
        self.d_col = jnp.where(self.lane == j, down, self.d_col)
        self.d_row = jnp.where(self.line == j, along, self.d_row)

    def write(self):
        self.out[0][...] = self.d_col
        self.out[1][...] = self.d_row


def _traced_once(fn):
    """``fn`` of arrays (or trees of them), traced once a signature: a
    kernel's body calls it once a head of its step, and every call after the
    first binds the recorded equations again (30 heads unrolled op by op
    through ``jnp`` cost the cell's first call 6 s of tracing on the chip
    machine's host; as a jitted function a head cost the kernels' lowering 3
    s, an equation of its own to lower a head)."""
    traced = {}

    def call(*args):
        leaves, tree = jax.tree.flatten(args)
        key = (tree, tuple((a.shape, a.dtype) for a in leaves))
        if key not in traced:
            traced[key] = jax.make_jaxpr(fn, return_shape=True)(
                *jax.tree.unflatten(tree, [jax.ShapeDtypeStruct(*of)
                                           for of in key[1]]))
        closed, shape = traced[key]
        return jax.tree.unflatten(
            jax.tree.structure(shape),
            jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *leaves))

    return call



# ------------------------------------------------------------------ A alone
@_traced_once
def _a_head(k, down, along):
    c = _Chunk(down, along)
    return jnp.where(c.strict, _dot(k, k, _NT) * c.gamma, 0.0)


def _a_kernel(k_ref, g_col_ref, g_row_ref, a_ref, *, dk: int):
    g = _Columns(g_col_ref, g_row_ref)
    for j in range(a_ref.shape[0]):
        a_ref[j] = _a_head(_head(k_ref, j, dk)[...], *g.of(j))


@_traced_once
def _a_backward_head(k, down, along, dA, dk_in):
    """-> (dk in k's dtype, ``dk_in`` (float32, what the scan's backward
    found for k) added in: the sum is taken here and rounded once; ``G``'s
    cotangent in its two layouts)."""
    dt = k.dtype
    c = _Chunk(down, along)
    dA = jnp.where(c.strict, dA, 0.0)
    products = (dA * c.gamma).astype(dt)            # K K^T's cotangent
    dk = dk_in + _dot(products, k) + _dot(products, k, _TN)
    return (dk.astype(dt),) + _sums(dA * _dot(k, k, _NT) * c.gamma)


def _a_backward_kernel(k_ref, g_col_ref, g_row_ref, da_ref, dk_in_ref,
                       dk_ref, dg_col_ref, dg_row_ref, *, dk: int):
    g = _Columns(g_col_ref, g_row_ref, dg_col_ref, dg_row_ref)
    for j in range(da_ref.shape[0]):
        _head(dk_ref, j, dk)[...], down, along = _a_backward_head(
            _head(k_ref, j, dk)[...], *g.of(j), da_ref[j],
            _head(dk_in_ref, j, dk)[...])
        g.put(j, down, along)
    g.write()


# ----------------------------------------- the states and the outputs: scan
class _Scaled:
    """A chunk's q and k of one head with their scaled copies and ``B``, as
    both scan kernels form them."""

    def __init__(self, q, k, down, along, dv: int):
        dt = q.dtype
        self.c = c = _Chunk(down, along).scales(dv)
        self.q, self.k = q, k
        q32, k32 = q.astype(_F32), k.astype(_F32)
        self.k_bar, self.q_bar = k32 * c.grow, q32 * c.grow
        self.k_end = k32 * c.to_end
        self.k_bar_low, self.q_bar_low, self.k_end_low = (
            a.astype(dt) for a in (self.k_bar, self.q_bar, self.k_end))
        # zero above the diagonal (Gamma is), float32 and as multiplied
        self.B = _dot(q, k, _NT) * c.gamma
        self.B_low = self.B.astype(dt)

    def solved(self, T, v, state_low):
        """-> (U = T V - (T Kbar) S_0 and T Kbar in q's dtype, Qbar S_0
        float32), rounded where the XLA form rounds them."""
        C = self.c.C
        tk = _dot(T, self.k_bar_low).astype(v.dtype)
        read = _dot(_rows(tk, self.q_bar_low), state_low)
        return (_dot(T, v) - read[:C]).astype(v.dtype), tk, read[C:]


@_traced_once
def _outputs_head(q, k, v, down, along, T, state):
    """``state``: (dk, dv) float32, what came into the chunk.  -> (o, the
    state that leaves it)."""
    dt = q.dtype
    x = _Scaled(q, k, down, along, v.shape[1])
    u, _, read = x.solved(T, v, state.astype(dt))
    return ((read + _dot(x.B_low, u)).astype(dt),
            state * x.c.through + _dot(x.k_end_low, u, _TN))


def _outputs_kernel(q_ref, k_ref, v_ref, g_col_ref, g_row_ref, t_ref, o_ref,
                    incoming_ref, s_scr, *, dk: int, dv: int):
    @pl.when(pl.program_id(2) == 0)
    def _a_rows_first_chunk():
        s_scr[...] = jnp.zeros(s_scr.shape, s_scr.dtype)

    g = _Columns(g_col_ref, g_row_ref)
    for j in range(t_ref.shape[0]):
        state = incoming_ref[j] = s_scr[j]
        _head(o_ref, j, dv)[...], s_scr[j] = _outputs_head(
            _head(q_ref, j, dk)[...], _head(k_ref, j, dk)[...],
            _head(v_ref, j, dv)[...], *g.of(j), t_ref[j], state)


@_traced_once
def _outputs_backward_head(q, k, v, down, along, T, state, dO, d_next):
    """``d_next``: (dk, dv) float32, the cotangent of the state that leaves
    the chunk.  -> (dq in q's dtype; dk float32, the scan's share; dv; ``G``'s
    cotangent in its two layouts; dT float32; the cotangent of the state
    that came in)."""
    dt = q.dtype
    x = _Scaled(q, k, down, along, v.shape[1])
    c, C = x.c, x.c.C
    state_low, d_next_low = state.astype(dt), d_next.astype(dt)
    u, tk, _ = x.solved(T, v, state_low)            # the forward again
    # o = Qbar S_0 + B U;  S_C = through S_0 + k_end^T U
    dU = (_dot(x.B_low, dO, _TN) + _dot(x.k_end_low, d_next_low)).astype(dt)
    dB = jnp.where(c.lower, _dot(dO, u, _NT), 0.0)
    d_k_end = _dot(u, d_next_low, _NT)
    # U = T V - (T Kbar) S_0 and Qbar S_0: what reached the state
    reached = _rows(-dU, dO)
    reads = _dot(reached, state_low, _NT)           # d(T Kbar) over dQbar
    d_state = d_next * c.through + _dot(_rows(tk, x.q_bar_low), reached, _TN)
    d_tk = reads[:C].astype(dt)
    dT = _dot(dU, v, _NT) + _dot(d_tk, x.k_bar_low, _NT)
    dv = _dot(T, dU, _TN).astype(dt)
    d_k_bar, d_q_bar = _dot(T, d_tk, _TN), reads[C:]
    # B = tril(Q K^T) * Gamma
    products = (dB * c.gamma).astype(dt)
    dq = (_dot(products, k) + d_q_bar * c.grow).astype(dt)
    dk = _dot(products, q, _TN) + d_k_bar * c.grow + d_k_end * c.to_end
    # G: the pairs of B, each position's own scales, and at the chunk's last
    # position what left through G_C
    down, along = _sums(dB * x.B)
    to_end = jnp.sum(d_k_end * x.k_end, axis=1, keepdims=True)
    down += jnp.sum(d_k_bar * x.k_bar + d_q_bar * x.q_bar, axis=1,
                    keepdims=True) - to_end
    d_last = jnp.sum(to_end, axis=0, keepdims=True) + jnp.sum(
        jnp.sum(d_next * state * c.through, axis=1, keepdims=True), axis=0,
        keepdims=True)
    row = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    down = jnp.where(row == C - 1, down + d_last, down)
    return dq, dk, dv, down, along, dT, d_state


def _outputs_backward_kernel(q_ref, k_ref, v_ref, g_col_ref, g_row_ref,
                             t_ref, incoming_ref, do_ref,
                             dq_ref, dk_ref, dv_ref, dg_col_ref, dg_row_ref,
                             dt_ref, ds_scr, *, dk: int, dv: int):
    @pl.when(pl.program_id(2) == 0)  # the row's last chunk
    def _no_state_leaves_a_row():
        ds_scr[...] = jnp.zeros(ds_scr.shape, ds_scr.dtype)

    g = _Columns(g_col_ref, g_row_ref, dg_col_ref, dg_row_ref)
    for j in range(t_ref.shape[0]):
        (_head(dq_ref, j, dk)[...], _head(dk_ref, j, dk)[...],
         _head(dv_ref, j, dv)[...], down, along, dt_ref[j],
         ds_scr[j]) = _outputs_backward_head(
            _head(q_ref, j, dk)[...], _head(k_ref, j, dk)[...],
            _head(v_ref, j, dv)[...], *g.of(j), t_ref[j], incoming_ref[j],
            _head(do_ref, j, dv)[...], ds_scr[j])
        g.put(j, down, along)
    g.write()


# ---------------------------------------------------------------- the calls
class _Sizes:
    """The extents of a call and the block specs of its arrays.  q, k, v, o
    and their cotangents are (b, S, H x d), the projections' layout; ``G``
    and its cotangent come as (b, H / heads, S, heads) and (b, n, H / heads,
    heads, C); a chunk's matrices as (b, n, H, C, C) and its incoming states
    as (b, n, H, dk, dv)."""

    def __init__(self, k, v, chunk: int, heads: int, H: int):
        self.b, self.S = k.shape[:2]
        self.H, self.chunk, self.heads = H, chunk, heads
        self.dk, self.dv = k.shape[-1] // H, v.shape[-1] // H
        self.n = self.S // chunk

    @property
    def grid(self):
        """(rows, steps of ``heads`` heads, chunks)."""
        return self.b, self.H // self.heads, self.n

    def specs(self, at):
        """(keys', values', ``G`` down, ``G`` along, squares', states')
        block specs; ``at`` maps the chunk axis' step to the chunk."""
        C, heads = self.chunk, self.heads

        def wide(d):
            return pl.BlockSpec((None, C, heads * d),
                                lambda i, h, c: (i, at(c), h))

        def per_chunk(*block):
            return pl.BlockSpec((None, None, heads) + block,
                                lambda i, h, c: (i, at(c), h, 0, 0))

        return (wide(self.dk), wide(self.dv),
                pl.BlockSpec((None, None, C, heads),
                             lambda i, h, c: (i, h, at(c), 0)),
                pl.BlockSpec((None, None, None, heads, C),
                             lambda i, h, c: (i, at(c), h, 0, 0)),
                per_chunk(C, C), per_chunk(self.dk, self.dv))

    def squares(self, dtype):
        return jax.ShapeDtypeStruct(
            (self.b, self.n, self.H, self.chunk, self.chunk), dtype)

    def states(self):
        return jax.ShapeDtypeStruct(
            (self.b, self.n, self.H, self.dk, self.dv), _F32)

    def scratch(self):
        """A grid step's heads' states (or their cotangents), float32."""
        return pltpu.VMEM((self.heads, self.dk, self.dv), _F32)

    def backward(self, dtype):
        """Of the scan's backward, the widest call: (in specs, out specs, the
        arrays' dtypes in that order): q, k, v, ``G`` twice, T, the incoming
        states, dO -> dq, dk (float32), dv, ``G``'s cotangent twice, dT."""
        keys, values, col, row, square, states = self.specs(
            lambda c: self.n - 1 - c)
        return ([keys, keys, values, col, row, square, states, values],
                [keys, keys, values, col, row, square],
                [dtype] * 3 + [_F32] * 2 + [dtype, _F32, dtype]
                + [dtype, _F32, dtype] + [_F32] * 3)


#: the v5e's default scoped VMEM; what a kernel's own values may take beside
#: its blocks (the heads' chains are unrolled side by side); and the most a
#: call asks for (of the chip's 128 MiB)
VMEM_SCOPE = 16 * 2 ** 20
VMEM_SPARE = 12 * 2 ** 20
VMEM_MOST = 64 * 2 ** 20


def _vmem(specs, dtypes, scratch=()):
    """Bytes a call asks of VMEM: a grid step's blocks as VMEM holds them
    (the last dimension padded to whole lane tiles), twice for the
    pipeline's two buffers, the scratch, and :data:`VMEM_SPARE`."""
    def padded(shape, dtype):
        dims = [d for d in shape if d is not None]
        return math.prod(dims[:-1]) * -(-dims[-1] // LANES) * LANES \
            * jnp.dtype(dtype).itemsize

    return (2 * sum(padded(s.block_shape, d) for s, d in zip(specs, dtypes))
            + sum(padded(s.shape, s.dtype) for s in scratch) + VMEM_SPARE)


def _call(kernel, name: str, z: _Sizes, semantics, args, in_specs,
          out_shape, out_specs, scratch=()):
    """The ``pallas_call``: past the chip's default scope it asks for the
    VMEM its blocks take."""
    asked = _vmem(in_specs + out_specs,
                  [a.dtype for a in args] + [o.dtype for o in out_shape],
                  scratch)
    return pl.pallas_call(
        kernel, grid=z.grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=max(asked, VMEM_SCOPE)),
        interpret=_interpret(), name=name)(*args)


def _like(a, dtype=None):
    return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype)


_PARALLEL = ("parallel", "parallel", "parallel")
_SEQUENTIAL = ("parallel", "parallel", "arbitrary")  # the chunks in order


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "H"))
def a_forward(k, g_col, g_row, chunk: int, heads: int, H: int):
    """k as :func:`_lay` gives it; the two layouts of ``G``.  -> A (b, n, H,
    C, C) float32, zero on and above the diagonal.  Jitted, as the other three
    are: a model's layers then share one traced and lowered kernel a
    signature."""
    z = _Sizes(k, k, chunk, heads, H)
    keys, _, col, row, square, _ = z.specs(lambda c: c)
    return _call(functools.partial(_a_kernel, dk=z.dk), "gdn_a_forward", z,
                 _PARALLEL, (k, g_col, g_row), [keys, col, row],
                 [z.squares(_F32)], [square])[0]


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "H"))
def a_backward(k, g_col, g_row, dA, dk, chunk: int, heads: int, H: int):
    """-> (dk in k's dtype, the scan's share ``dk`` (float32) added in; its
    shares of ``G``'s cotangent in the two layouts, float32)."""
    z = _Sizes(k, k, chunk, heads, H)
    keys, _, col, row, square, _ = z.specs(lambda c: c)
    return _call(functools.partial(_a_backward_kernel, dk=z.dk),
                 "gdn_a_backward", z, _PARALLEL, (k, g_col, g_row, dA, dk),
                 [keys, col, row, square, keys],
                 [_like(k), _like(g_col), _like(g_row)], [keys, col, row])


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "H"))
def outputs_forward(q, k, v, g_col, g_row, T, chunk: int, heads: int, H: int):
    """-> (o, laid out as v; the state that came into each chunk (b, n, H,
    dk, dv) float32, for the backward: a forward that did not write them
    would be a fifth kernel for a first call to trace and lower, for 0.35 ms
    a layer in `olmo-hybrid-s8192`)."""
    z = _Sizes(k, v, chunk, heads, H)
    keys, values, col, row, square, states = z.specs(lambda c: c)
    return _call(functools.partial(_outputs_kernel, dk=z.dk, dv=z.dv),
                 "gdn_scan_forward", z, _SEQUENTIAL,
                 (q, k, v, g_col, g_row, T),
                 [keys, keys, values, col, row, square],
                 [_like(v), z.states()], [values, states], [z.scratch()])


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "H"))
def outputs_backward(q, k, v, g_col, g_row, T, incoming, dO, chunk: int,
                     heads: int, H: int):
    """-> (dq in q's dtype; dk float32, the scan's share, for
    :func:`a_backward`; dv; the scan's shares of ``G``'s cotangent in the two
    layouts; dT float32)."""
    z = _Sizes(k, v, chunk, heads, H)
    in_specs, out_specs, _ = z.backward(q.dtype)
    return _call(functools.partial(_outputs_backward_kernel, dk=z.dk,
                                   dv=z.dv),
                 "gdn_scan_backward", z, _SEQUENTIAL,
                 (q, k, v, g_col, g_row, T, incoming, dO), in_specs,
                 [_like(q), _like(k, _F32), _like(v), _like(g_col),
                  _like(g_row), z.squares(_F32)], out_specs, [z.scratch()])


# ------------------------------------------------------------ the operation
def heads_a_step(H: int, dk: int, dv: int) -> int:
    """Heads a grid step takes.  A step's columns must be whole lane tiles
    or all of them: the largest divisor of H up to eight whose keys and
    values are, else all H (30 heads of 96 under 192: no divisor's keys
    fill tiles)."""
    fits = [j for j in range(1, min(H, 8) + 1) if H % j == 0
            and j * dk % LANES == 0 and j * dv % LANES == 0]
    return max(fits, default=H)


def fits(H: int, dk: int, dv: int, chunk: int, itemsize: int = 2) -> bool:
    """Whether the widest call's blocks (the scan's backward, at
    :func:`heads_a_step`'s heads a step) lie inside :data:`VMEM_MOST`."""
    dtype = {2: jnp.bfloat16, 4: _F32}[itemsize]
    z = _Sizes(jax.ShapeDtypeStruct((1, chunk, H * dk), dtype),
               jax.ShapeDtypeStruct((1, chunk, H * dv), dtype), chunk,
               heads_a_step(H, dk, dv), H)
    in_specs, out_specs, dtypes = z.backward(dtype)
    return _vmem(in_specs + out_specs, dtypes, [z.scratch()]) <= VMEM_MOST


def grid(q, v, chunk: int):
    """(rows, steps of heads, chunks): the extents the kernels walk on q
    (b, S, H, dk) and v (b, S, H, dv)."""
    b, S, H, dk = q.shape
    return b, H // heads_a_step(H, dk, v.shape[-1]), S // chunk


def _two_layouts(G, heads: int):
    """G (b, n, C, H) -> (b, H / heads, S, heads) and (b, n, H / heads,
    heads, C)."""
    b, n, C, H = G.shape
    return (jnp.moveaxis(G.reshape(b, n * C, H // heads, heads), 2, 1),
            jnp.moveaxis(G.reshape(b, n, C, H // heads, heads), 2, 4))


def _one_layout(col, row):
    """:func:`_two_layouts` back, the two summed: -> (b, n, C, H)."""
    b, n, groups, heads, C = row.shape
    return (jnp.moveaxis(col, 1, 2).reshape(b, n, C, groups * heads)
            + jnp.moveaxis(row, 4, 2).reshape(b, n, C, groups * heads))


def _lay(x):
    """(b, S, H, d) as the kernels take it: the heads' columns side by
    side, no copy."""
    b, S, H, d = x.shape
    return x.reshape(b, S, H * d)


def _unlay(x, H: int):
    b, S, width = x.shape
    return x.reshape(b, S, H, width // H)


def _forward(q, k, v, g, beta, chunk, heads):
    b, S, H, dk = q.shape
    heads = heads or heads_a_step(H, dk, v.shape[-1])
    q, k, v = (_lay(a) for a in (q, k, v))
    # the cumulative sum over each chunk by itself, as ``gdn_xla`` forms it
    G = within_chunks(g.astype(_F32), chunk).reshape(b, S // chunk, chunk, H)
    g_col, g_row = _two_layouts(G, heads)
    A = a_forward(k, g_col, g_row, chunk, heads, H)
    # beta a chunk and a head, (b, n, H, C): it scales N's rows and T's
    # columns and never enters a kernel
    beta = jnp.moveaxis(beta.astype(_F32).reshape(b, S // chunk, chunk, H),
                        3, 2)
    # what the layer's checkpoint may keep (``ops/remat.py``): the backward
    # reads both, and a second forward then runs no substitution
    X = checkpoint_name(_unit_lower_inverse(A * beta[..., None]),
                        remat.INVERSE)
    T = checkpoint_name((X * beta[..., None, :]).astype(q.dtype),
                        remat.INVERSE)
    o, incoming = outputs_forward(q, k, v, g_col, g_row, T, chunk, heads, H)
    return _unlay(o, H), (q, k, v, g_col, g_row, beta, A, X, T, incoming)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def scan(q, k, v, g, beta, chunk: int, heads: int | None = None):
    """``ops.gdn.gdn``'s arguments and result, by the kernels.  ``heads`` a
    grid step (None: :func:`heads_a_step`) is what
    ``scripts/gdn_scan_sweep.py`` varies."""
    return _forward(q, k, v, g, beta, chunk, heads)[0]


def _scan_fwd(q, k, v, g, beta, chunk, heads):
    o, saved = _forward(q, k, v, g, beta, chunk, heads)
    # of g and beta their cotangents' types alone
    return o, (saved, jnp.zeros((), g.dtype), jnp.zeros((), beta.dtype))


def _scan_bwd(chunk, heads, saved, dO):
    # traced under the name stack of the call it is the backward of: the
    # caller's ``gdn_scan`` scope names these calls too
    (q, k, v, g_col, g_row, beta, A, X, T, incoming), like_g, like_beta = saved
    b, S, H, _ = dO.shape
    heads = g_col.shape[-1]
    dq, dk, dv, dg_col, dg_row, dT = outputs_backward(
        q, k, v, g_col, g_row, T, incoming, _lay(dO), chunk, heads, H)
    # T = X diag(beta), X = (I + diag(beta) A)^-1
    dN = inverse_backward(X, dT * beta[..., None, :])
    d_beta = jnp.sum(dT * X, axis=-2) + jnp.sum(dN * A, axis=-1)
    dk, dg_col_a, dg_row_a = a_backward(k, g_col, g_row, dN * beta[..., None],
                                        dk, chunk, heads, H)
    # the sum's cotangent summed back from each chunk's end
    after = np.triu(np.ones((chunk, chunk), np.float32))   # [t, s]: s >= t
    dg = jnp.einsum("ts,bnsh->bnth", after,
                    _one_layout(dg_col + dg_col_a, dg_row + dg_row_a),
                    precision=lax.Precision.HIGHEST)
    d_beta = jnp.moveaxis(d_beta, 2, 3).reshape(b, S, H)
    return (_unlay(dq, H), _unlay(dk, H), _unlay(dv, H),
            dg.reshape(b, S, H).astype(like_g.dtype),
            d_beta.astype(like_beta.dtype))


scan.defvjp(_scan_fwd, _scan_bwd)
