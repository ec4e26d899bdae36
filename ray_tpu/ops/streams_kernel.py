"""The hyper-connections of ``models/streams.py`` (the maps with the read,
the write, and their backwards) as four Pallas (Mosaic) passes that tile the
positions and hold a position's whole row of ``C`` lanes of every stream in
VMEM: each stream-sized array crosses HBM once a pass, and what a position
needs between (the norm's sum, the logits, the sigmoids, the Sinkhorn turns,
the n x n mix) stays on the chip.  XLA's form of the same expressions made
127 element-wise passes a step over a stream-sized array and read the
streams twice more for the maps than the read does (PERF.md, PR 64).

**The passes**, n = 4 streams ``X`` of (tokens, C), arrays moved beside
``benchmarks/lib/cost_xing.py:mix_bytes``' least count:

1. :func:`maps_read` forward (and a layer checkpoint's second forward):
   reads ``X``; float32 sum of squares, the logits ``x_j . phi_j`` summed
   over the streams (compute dtype, float32 accumulation), the scale by
   ``rsqrt``, ``alpha``, ``base``, the sigmoids, ``exp(clip())`` and the
   turns in float32; writes ``H`` (tokens, n^2 + 2n) float32 and ``u``
   (and, for pass 4, the raw products and the squares' sum: 33 float32 a
   position).  n + 1 arrays.
2. :func:`write` forward: reads ``X``, ``y`` and ``H``, writes the n new
   streams, float32 multiply-adds rounded once.  2n + 1.
3. the write's backward: reads the cotangents of ``X'``, ``X``, ``y`` and
   ``H``; writes ``y``'s cotangent, the stream map's share of ``X``'s and
   the float32 cotangents of ``H_post`` and ``H_res`` (n^2 + n sums over a
   row's lanes, added up lane tile by lane tile and reduced across the
   lanes once a row).  3n + 2.
4. the read's and the maps' backward: reads ``u``'s cotangent, ``X``, that
   share, ``H`` and ``H``'s cotangent; the logits again from what pass 1
   kept (the product with ``phi`` is not made twice), ``H_pre``'s cotangent, the turns' reverse pass (every turn made again from its
   input, which the forward turns leave in VMEM: no implicit gradient), the
   cotangents of the logits and of the norm's sum; writes ``X``'s cotangent
   (its three readers' terms summed in float32, rounded once) and adds up
   ``phi``'s, ``alpha``'s and ``base``'s across the grid's one sequential
   axis.  3n + 1.

So that pass 4 meets the write's share of ``X``'s cotangent, ``maps_read``
hands the streams on as its third result and the write reads those: the
share comes back as that result's cotangent and nothing is added outside.
Each pair is a ``jax.custom_vjp`` whose residuals are its own inputs, ``H``
and pass 1's 33 numbers a position: nothing as wide as a stream.

**Layout.**  A grid step holds :func:`block` positions (a multiple of 128)
and walks them in groups of :data:`ROWS` rows a lane tile at a time (a
tile's arithmetic traced once, ``gdn_kernel._traced_once``; the tiles of a
row a loop, :func:`_walk`), each stream
converted to float32 once a tile and reused by every product that reads it.
What is a number a position lives two ways: *rows* (positions on sublanes,
the maps' n^2 + 2n numbers on lanes: how ``H`` crosses HBM, and how a weight
is spread over a position's lanes) and *lanes* ((:data:`WIDE`, positions):
the logits leave the matrix unit so, ``phi^T x^T``, and the turns run on
whole vector registers, the n^2 entries of the stream map on two sublane
tiles whose column sums are one add and one rotation and whose row sums two
rotations and two selects); a (positions, 128) float32 transpose goes
between.  The turns are a ``fori_loop`` of a fixed count.

**Where it runs** (:func:`path`): on the chip, n = 4 (the turns' layout),
no mesh or a mesh of one device, ``C`` whole lane tiles, the tokens whole
blocks; ``models/streams.py``'s expressions everywhere else (the CPU, a
mesh, the tests' oracle).  Not a TPU: Pallas' interpret mode, which only a
test asks for (:func:`on_chip`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.util import first_call

LANES = 128
#: positions a group of the in-kernel loop: one packed bf16 sublane tile
ROWS = 16
#: a sublane tile of float32
TILE = 8
#: the maps' n^2 + 2n numbers padded to whole sublane tiles: the rows of the
#: lanes layout and of ``phi^T``
WIDE = 32
#: the streams the turns' layout is written for
STREAMS = 4
#: lane tiles a turn of the loop over a row's lanes
UNROLL = 4
#: positions a grid step, the largest first
BLOCKS = (256, 128)
#: the chip's default scope of VMEM, and the most a call asks for
VMEM_SCOPE = 16 << 20
VMEM_MOST = 96 << 20
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))


def on_chip() -> bool:
    """Whether the kernels are programs of this backend.  Off the chip the
    expressions of ``models/streams.py`` are the hyper-connections; a test
    that wants the interpreter's run of the kernels replaces this
    function."""
    return jax.default_backend() == "tpu"


def _vmem(rows: int, C: int, n: int, itemsize: int) -> int:
    """What the widest pass (4) asks of VMEM at ``rows`` positions a step:
    its 3n + 1 blocks in two buffers, ``phi^T`` and its cotangent, the
    float32 products of the streams and the small scratch."""
    wide = rows * C
    return (2 * (3 * n + 1) * wide * itemsize + 2 * n * WIDE * C * (
        itemsize + 4) + n * wide * 4 + (8 << 20))


def block(tokens: int, C: int, n: int, itemsize: int) -> Optional[int]:
    """-> the positions of a grid step over ``tokens`` positions of n
    streams ``C`` wide, or None where no block does."""
    if n != STREAMS or C % LANES:
        return None
    return next((rows for rows in BLOCKS if tokens % rows == 0
                 and _vmem(rows, C, n, itemsize) <= VMEM_MOST), None)


def path(tokens: int, C: int, n: int, itemsize: int, mesh) -> str:
    """-> ``"kernel"`` or ``"xla"``: which form the hyper-connections over
    n streams of ``tokens`` positions ``C`` wide take under ``mesh``."""
    if on_chip() and (mesh.empty or mesh.size == 1) \
            and block(tokens, C, n, itemsize):
        return "kernel"
    return "xla"  # the backend, a mesh, or the shapes


def engaged(X) -> bool:
    """From ``streams.layer``, while a sub-layer is traced: whether its
    hyper-connections are the kernels', here and now; noted in the
    first-call record."""
    C = X[0].shape[-1]
    kernel = path(X[0].size // C, C, len(X), X[0].dtype.itemsize,
                  jax.sharding.get_abstract_mesh()) == "kernel"
    first_call.note(streams_kernel="kernel" if kernel else "xla")
    first_call.count("mhc_calls")
    return kernel


# ------------------------------------------------------- a tile's arithmetic
def _traced(fn):
    """``gdn_kernel._traced_once`` of ``fn``, made at its first call (that
    module imports Pallas)."""
    @functools.lru_cache(maxsize=None)
    def once():
        from ray_tpu.ops.gdn_kernel import _traced_once

        return _traced_once(fn)

    return lambda *args: once()(*args)


def _folded(terms):
    """Sum of a list, left to right from the first."""
    return functools.reduce(lambda a, b: a + b, terms)


@_traced
def _squares_tile(acc, xs):
    for x in xs:
        x = x.astype(_F32)
        acc = acc + x * x
    return acc


@_traced
def _read_tile(pre, xs):
    """``H_pre X``: float32."""
    return _folded([w * x.astype(_F32) for w, x in zip(pre, xs)])


@_traced
def _write_tile(res, post, xs, y):
    """``H_res X + H_post^T y``: the n new streams' tile, float32.  ``res``
    row-major, n a row."""
    n = len(xs)
    xs = [x.astype(_F32) for x in xs]
    y = y.astype(_F32)
    return [_folded([res[i * n + j] * xs[j] for j in range(n)])
            + post[i] * y for i in range(n)]


@_traced
def _write_back_tile(res, post, sums, gs, xs, y):
    """The write's backward over a tile.  ``gs``: the cotangents of the new
    streams; ``sums``: the n + n^2 running sums of ``H_post``'s and
    ``H_res``'s cotangents.  -> (``y``'s cotangent, the share of each
    stream's, float32; the sums)."""
    n = len(xs)
    gs = [g.astype(_F32) for g in gs]
    xs = [x.astype(_F32) for x in xs]
    y = y.astype(_F32)
    dy = _folded([post[i] * gs[i] for i in range(n)])
    share = [_folded([res[i * n + j] * gs[i] for i in range(n)])
             for j in range(n)]
    sums = [sums[i] + gs[i] * y for i in range(n)] + [
        sums[n + i * n + j] + gs[i] * xs[j]
        for i in range(n) for j in range(n)]
    return dy, share, sums


@_traced
def _read_back_sums_tile(sums, du, xs):
    """Pass 4's first walk: ``H_pre``'s cotangent, a sum a stream."""
    du = du.astype(_F32)
    return [s + du * x.astype(_F32) for x, s in zip(xs, sums)]


@_traced
def _read_back_tile(pre, twice, du, xs, shares, products):
    """Pass 4's second walk: a stream's cotangent is the read's term, the
    write's share, the norm's (``twice``: twice the cotangent of the sum of
    squares) and the logits' product with ``phi``."""
    du = du.astype(_F32)
    return [pre[j] * du + shares[j].astype(_F32) + twice * xs[j].astype(_F32)
            + products[j] for j in range(len(xs))]


# ------------------------------------------------- the maps, positions on lanes
def _roll(a, shift: int):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(a, shift, 0)


def _column_sums(top, bottom):
    """Of the stream map as two sublane tiles (rows 0, 1 | rows 2, 3 of
    it, an entry a sublane, row-major): each entry's column's sum, (8,
    positions), the same for both tiles."""
    half = top + bottom
    return half + _roll(half, STREAMS)


def _row_sums(tile):
    """Each entry's row's sum within one such tile."""
    row = lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    pair = tile + jnp.where(row % 2 == 0, _roll(tile, TILE - 1),
                            _roll(tile, 1))
    return pair + jnp.where(row % 4 < 2, _roll(pair, TILE - 2),
                            _roll(pair, 2))


def _turn(m, eps: float):
    """A column and then a row normalisation of (top, bottom)."""
    top, bottom = m
    columns = _column_sums(top, bottom) + eps
    top, bottom = top / columns, bottom / columns
    return top / (_row_sums(top) + eps), bottom / (_row_sums(bottom) + eps)


def _turn_back(m, g, eps: float):
    """The cotangent of a turn's input ``m`` from its output's, ``g``: the
    turn made again, then its two divisions' reverse."""
    top, bottom = m
    columns = _column_sums(top, bottom) + eps
    a = top / columns, bottom / columns
    rows = [_row_sums(part) + eps for part in a]
    da = [(g_ - _row_sums(g_ * (a_ / r))) / r
          for g_, a_, r in zip(g, a, rows)]
    pulled = _column_sums(da[0] * a[0], da[1] * a[1])
    return (da[0] - pulled) / columns, (da[1] - pulled) / columns


def _gates(logits):
    """(8, positions) logits of ``H_pre | H_post`` -> (the gates, their
    derivative by the logits)."""
    s = jax.nn.sigmoid(logits)
    twice = lax.broadcasted_iota(jnp.int32, logits.shape, 0) >= STREAMS
    return jnp.where(twice, 2.0 * s, s), \
        jnp.where(twice, 2.0, 1.0) * s * (1.0 - s)


def _across(rows_layout):
    """(positions, 128) -> (128, positions), or back."""
    return rows_layout.T


def _in_rows(*parts):
    """Arrays of (whole sublane tiles, positions), one under the other and
    zeros under them to 128 -> (positions, 128): a position's numbers side
    by side.  A part of one row is spread over a tile first."""
    R = parts[0].shape[1]
    parts = [jnp.broadcast_to(p, (TILE, R)) if p.shape[0] == 1 else p
             for p in parts]
    held = sum(p.shape[0] for p in parts)
    return _across(jnp.concatenate(
        parts + [jnp.zeros((LANES - held, R), _F32)]))


def _products(x_refs, phi_ref, squares_ref):
    """-> (the raw products ``phi^T x^T`` summed over the streams, (WIDE,
    positions) float32; the sum of a position's squares, (1, positions))."""
    raw = _folded([lax.dot_general(phi_ref[j], x_refs[j][...], _NT,
                                   preferred_element_type=_F32)
                   for j in range(len(x_refs))])
    return raw, jnp.sum(_across(squares_ref[...]), axis=0, keepdims=True)


def _logits(raw, squares, coef_ref, lanes: int, rms_eps: float):
    """-> (the norm's scale (1, positions), the products scaled, the
    logits); ``lanes``: n C, what the squares' mean is over."""
    scale = lax.rsqrt(squares / lanes + rms_eps)
    scaled = raw * scale
    return scale, scaled, scaled * coef_ref[:, 0:1] + coef_ref[:, 1:2]


def _rows_at(g):
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS)


def _walk(C: int, step, carry=0):
    """``carry = step(cols, carry)`` for every lane tile ``cols`` of ``C``
    lanes: a loop whose turn holds :data:`UNROLL` tiles where they divide
    the tiles (a row's 28 tiles written out made the four kernels' text
    seven times as long, and a step's first call traces, lowers and
    compiles every line of it; PERF.md, PR 64)."""
    from jax.experimental import pallas as pl

    tiles = C // LANES
    unroll = next(u for u in (UNROLL, 2, 1) if tiles % u == 0)

    def turn(c, carry):
        for t in range(unroll):
            carry = step(pl.ds(pl.multiple_of((c * unroll + t) * LANES,
                                              LANES), LANES), carry)
        return carry

    return lax.fori_loop(0, tiles // unroll, turn, carry)


def _spread(rows_layout, count: int, first: int = 0):
    """A group's (ROWS, 128) numbers -> ``count`` of them from lane
    ``first``, each spread over a tile's lanes."""
    return [jnp.broadcast_to(rows_layout[:, k:k + 1], (ROWS, LANES))
            for k in range(first, first + count)]


def _gathered(sums, first: int):
    """(ROWS, 128) running sums -> one (ROWS, 128): sum ``k`` reduced over
    its lanes into lane ``first + k``, zero elsewhere."""
    lane = lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1)
    out = jnp.zeros((ROWS, LANES), _F32)
    for k, s in enumerate(sums):
        out = jnp.where(lane == first + k,
                        jnp.sum(s, axis=1, keepdims=True), out)
    return out


# ------------------------------------------------------------------ pass 1
def _maps_read_kernel(*refs, n: int, iters: int, eps: float, clamp,
                      rms_eps: float):
    """refs: the n streams' blocks, ``phi^T`` (n, WIDE, C), (WIDE, 2)
    ``alpha | base``; ``H``'s block, ``u``'s, and (positions, 128) float32
    for the backward: the raw products on the first WIDE lanes, the squares'
    sum on the next; scratch (positions, 128) float32: the squares' sums a
    lane, then ``H`` in rows."""
    x_refs, (phi_ref, coef_ref, h_ref, u_ref, kept_ref, squares_ref,
             rows_ref) = refs[:n], refs[n:]
    R, C = x_refs[0].shape
    W = h_ref.shape[1]

    def squares(g, _):
        at = _rows_at(g)
        squares_ref[at, :] = _walk(
            C, lambda cols, acc: _squares_tile(
                acc, [x[at, cols] for x in x_refs]),
            jnp.zeros((ROWS, LANES), _F32))
        return 0

    lax.fori_loop(0, R // ROWS, squares, 0)
    raw, squares = _products(x_refs, phi_ref, squares_ref)
    kept_ref[...] = _in_rows(raw, squares)
    logits = _logits(raw, squares, coef_ref, n * C, rms_eps)[2]
    gates = _gates(logits[:TILE])[0]
    start = jnp.exp(jnp.clip(logits[TILE:3 * TILE], *clamp))
    top, bottom = lax.fori_loop(
        0, iters, lambda _, m: _turn(m, eps), (start[:TILE], start[TILE:]))
    rows_ref[...] = _in_rows(gates, top, bottom)
    h_ref[...] = rows_ref[:, :W]

    def mix(g, _):
        at = _rows_at(g)
        pre = _spread(rows_ref[at, :], n)

        def tile(cols, _):
            u_ref[at, cols] = _read_tile(
                pre, [x[at, cols] for x in x_refs]).astype(u_ref.dtype)
            return 0

        return _walk(C, tile)

    lax.fori_loop(0, R // ROWS, mix, 0)


# ------------------------------------------------------------------ pass 2
def _write_kernel(*refs, n: int):
    """refs: the n streams' blocks, ``y``'s, ``H``'s; the n new streams'."""
    x_refs, y_ref, h_ref, out_refs = refs[:n], refs[n], refs[n + 1], \
        refs[n + 2:]
    R, C = y_ref.shape

    def mix(g, _):
        at = _rows_at(g)
        h = h_ref[at, :]
        post, res = _spread(h, n, n), _spread(h, n * n, 2 * n)

        def tile(cols, _):
            new = _write_tile(res, post, [x[at, cols] for x in x_refs],
                              y_ref[at, cols])
            for out, new_tile in zip(out_refs, new):
                out[at, cols] = new_tile.astype(out.dtype)
            return 0

        return _walk(C, tile)

    lax.fori_loop(0, R // ROWS, mix, 0)


# ------------------------------------------------------------------ pass 3
def _write_back_kernel(*refs, n: int):
    """refs: the n cotangents of the new streams, the n streams, ``y``,
    ``H``; ``y``'s cotangent, the n shares, ``H``'s cotangent (its first n
    lanes, ``H_pre``'s, zero)."""
    g_refs, x_refs, (y_ref, h_ref, dy_ref), share_refs, dh_ref = \
        refs[:n], refs[n:2 * n], refs[2 * n:2 * n + 3], \
        refs[2 * n + 3:3 * n + 3], refs[3 * n + 3]
    R, C = y_ref.shape
    W = dh_ref.shape[1]

    def mix(g, _):
        at = _rows_at(g)
        h = h_ref[at, :]
        post, res = _spread(h, n, n), _spread(h, n * n, 2 * n)

        def tile(cols, sums):
            dy, share, sums = _write_back_tile(
                res, post, sums, [g_[at, cols] for g_ in g_refs],
                [x[at, cols] for x in x_refs], y_ref[at, cols])
            dy_ref[at, cols] = dy.astype(dy_ref.dtype)
            for out, share_tile in zip(share_refs, share):
                out[at, cols] = share_tile.astype(out.dtype)
            return sums

        sums = _walk(C, tile, [jnp.zeros((ROWS, LANES), _F32)] * (n + n * n))
        dh_ref[at, :] = _gathered(sums, n)[:, :W]
        return 0

    lax.fori_loop(0, R // ROWS, mix, 0)


# ------------------------------------------------------------------ pass 4
def _read_back_kernel(*refs, n: int, iters: int, eps: float, clamp,
                      rms_eps: float):
    """refs: ``u``'s cotangent, the n streams, the n shares, ``H``, its
    cotangent, what pass 1 kept, ``phi^T``, ``alpha | base``; the n streams'
    cotangents, ``phi^T``'s (float32, summed over the grid) and (2, WIDE,
    128) float32 partial sums of ``alpha``'s (an entry's, before its group's
    sum) and ``base``'s; scratch: (positions, 128) float32 of ``H``'s
    cotangent in rows and of what the second walk spreads, the turns' inputs
    (iters, 16, positions), the streams' products (n, positions, C)."""
    from jax.experimental import pallas as pl

    du_ref, x_refs, share_refs = refs[0], refs[1:n + 1], refs[n + 1:2 * n + 1]
    (h_ref, dh_ref, kept_ref, phi_ref, coef_ref), dx_refs, \
        (dphi_ref, dcoef_ref), \
        (rows_ref, spread_ref, turns_ref, products_ref) = \
        refs[2 * n + 1:2 * n + 6], refs[2 * n + 6:3 * n + 6], \
        refs[3 * n + 6:3 * n + 8], refs[3 * n + 8:]
    R, C = du_ref.shape
    W = h_ref.shape[1]
    dt = x_refs[0].dtype

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)
        dcoef_ref[...] = jnp.zeros_like(dcoef_ref)

    rows_ref[...] = jnp.zeros_like(rows_ref)

    def sums(g, _):
        at = _rows_at(g)
        pre = _walk(
            C, lambda cols, pre: _read_back_sums_tile(
                pre, du_ref[at, cols], [x[at, cols] for x in x_refs]),
            [jnp.zeros((ROWS, LANES), _F32)] * n)
        rows_ref[at, :W] = _gathered(pre, 0)[:, :W] + dh_ref[at, :]
        return 0

    lax.fori_loop(0, R // ROWS, sums, 0)
    kept = _across(kept_ref[...])
    raw = kept[:WIDE]
    scale, scaled, logits = _logits(raw, kept[WIDE:WIDE + 1], coef_ref,
                                    n * C, rms_eps)
    dh = _across(rows_ref[...])                       # (128, positions)
    inside = logits[TILE:3 * TILE]
    start = jnp.exp(jnp.clip(inside, *clamp))

    def turn(t, m):
        turns_ref[t, :TILE], turns_ref[t, TILE:] = m
        return _turn(m, eps)

    lax.fori_loop(0, iters, turn, (start[:TILE], start[TILE:]))

    def turn_back(t, g):
        at = iters - 1 - t
        return _turn_back((turns_ref[at, :TILE], turns_ref[at, TILE:]), g,
                          eps)

    dtop, dbottom = lax.fori_loop(0, iters, turn_back,
                                  (dh[TILE:2 * TILE], dh[2 * TILE:3 * TILE]))
    free = (inside > clamp[0]) & (inside < clamp[1])
    dlogits = jnp.concatenate([
        dh[:TILE] * _gates(logits[:TILE])[1],
        jnp.where(free, jnp.concatenate([dtop, dbottom]) * start, 0.0),
        jnp.zeros((WIDE - 3 * TILE, R), _F32)])
    for k, part in enumerate((dlogits * scaled, dlogits)):
        dcoef_ref[k] += _folded([part[:, at:at + LANES]
                                 for at in range(0, R, LANES)])
    dscaled = dlogits * coef_ref[:, 0:1]
    draw = dscaled * scale
    dscale = jnp.sum(dscaled * raw, axis=0, keepdims=True)
    twice = dscale * scale * scale * scale * (-1.0 / (n * C))
    spread_ref[...] = _in_rows(draw, twice)
    low = draw.astype(dt)
    low_rows = spread_ref[:, :WIDE].astype(dt)
    for j in range(n):
        dphi_ref[j] += jnp.dot(low, x_refs[j][...],
                               preferred_element_type=_F32)
        products_ref[j] = jnp.dot(low_rows, phi_ref[j],
                                  preferred_element_type=_F32)

    def mix(g, _):
        at = _rows_at(g)
        pre = _spread(h_ref[at, :], n)
        twice = _spread(spread_ref[at, :], 1, WIDE)[0]

        def tile(cols, _):
            dx = _read_back_tile(
                pre, twice, du_ref[at, cols], [x[at, cols] for x in x_refs],
                [s[at, cols] for s in share_refs],
                [products_ref[j, at, cols] for j in range(n)])
            for out, dx_tile in zip(dx_refs, dx):
                out[at, cols] = dx_tile.astype(out.dtype)
            return 0

        return _walk(C, tile)

    lax.fori_loop(0, R // ROWS, mix, 0)


# ------------------------------------------------------------- the four calls
def _call(kernel, name: str, rows: int, args, blocked, outs, out_blocked,
          scratch=(), sequential: bool = False):
    """The ``pallas_call`` of one pass over ``rows`` positions a step.
    ``args`` / ``outs``: arrays / ``ShapeDtypeStruct``s; ``blocked`` /
    ``out_blocked``: whether each is walked in blocks of positions (its
    first axis) or whole every step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(a, walked):
        if walked:
            return pl.BlockSpec((rows,) + a.shape[1:],
                                lambda s: (s,) + (0,) * (a.ndim - 1))
        return pl.BlockSpec(a.shape, lambda s: (0,) * a.ndim)

    def padded(shape, dtype):
        shape = list(shape)
        shape[-1] = -(-shape[-1] // LANES) * LANES
        return int(np.prod(shape)) * jnp.dtype(dtype).itemsize

    in_specs = [spec(a, w) for a, w in zip(args, blocked)]
    out_specs = [spec(a, w) for a, w in zip(outs, out_blocked)]
    asked = 2 * sum(padded(s.block_shape, a.dtype)
                    for s, a in zip(in_specs + out_specs,
                                    list(args) + list(outs))) \
        + sum(padded(s.shape, s.dtype) for s in scratch) + (8 << 20)
    return pl.pallas_call(
        kernel, grid=(args[0].shape[0] // rows,), in_specs=in_specs,
        out_specs=out_specs, out_shape=list(outs),
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if sequential else "parallel",),
            vmem_limit_bytes=max(asked, VMEM_SCOPE)),
        interpret=jax.default_backend() != "tpu", name=name)(*args)


def _like(a, dtype=None, width=None):
    return jax.ShapeDtypeStruct(
        a.shape if width is None else (a.shape[0], width), dtype or a.dtype)


def _scratch(*shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, _F32)


@functools.partial(jax.jit, static_argnames=("statics",))
def _maps_read_call(X, phi_t, coef, statics):
    rows, width, iters, eps, clamp, rms_eps = statics
    n, T = len(X), X[0].shape[0]
    return _call(
        functools.partial(_maps_read_kernel, n=n, iters=iters, eps=eps,
                          clamp=clamp, rms_eps=rms_eps),
        "mhc_maps_read", rows, (*X, phi_t, coef), [True] * n + [False] * 2,
        [jax.ShapeDtypeStruct((T, width), _F32), _like(X[0]),
         jax.ShapeDtypeStruct((T, LANES), _F32)], [True] * 3,
        [_scratch(rows, LANES)] * 2)


@functools.partial(jax.jit, static_argnames=("rows",))
def _write_call(X, y, H, rows):
    n = len(X)
    return _call(functools.partial(_write_kernel, n=n), "mhc_write", rows,
                 (*X, y, H), [True] * (n + 2), [_like(x) for x in X],
                 [True] * n)


@functools.partial(jax.jit, static_argnames=("rows",))
def _write_back_call(G, X, y, H, rows):
    n = len(X)
    return _call(functools.partial(_write_back_kernel, n=n),
                 "mhc_write_backward", rows, (*G, *X, y, H),
                 [True] * (2 * n + 2),
                 [_like(y)] + [_like(x) for x in X] + [_like(H)],
                 [True] * (n + 2))


@functools.partial(jax.jit, static_argnames=("statics",))
def _read_back_call(du, X, shares, H, dH, kept, phi_t, coef, statics):
    rows, _, iters, eps, clamp, rms_eps = statics
    n, C = len(X), du.shape[1]
    return _call(
        functools.partial(_read_back_kernel, n=n, iters=iters, eps=eps,
                          clamp=clamp, rms_eps=rms_eps),
        "mhc_read_backward", rows,
        (du, *X, *shares, H, dH, kept, phi_t, coef),
        [True] * (2 * n + 4) + [False] * 2,
        [_like(x) for x in X] + [
            jax.ShapeDtypeStruct(phi_t.shape, _F32),
            jax.ShapeDtypeStruct((2, WIDE, LANES), _F32)],
        [True] * n + [False] * 2,
        [_scratch(rows, LANES)] * 2 + [
            _scratch(iters, 2 * TILE, rows), _scratch(n, rows, C)],
        sequential=True)


# --------------------------------------------------------- the two pairs
def _operands(phi, alpha, base, n: int, dtype):
    """Under ``mhc_maps``: ``phi`` (n C, W) -> ``phi^T`` (n, WIDE, C) in the
    compute dtype, and (WIDE, 2) float32 ``alpha`` (an entry's) beside
    ``base``, zero past W."""
    W = phi.shape[1]
    phi_t = jnp.pad(phi.reshape(n, -1, W).astype(dtype).transpose(0, 2, 1),
                    ((0, 0), (0, WIDE - W), (0, 0)))
    alpha = alpha[np.repeat(np.arange(3), (n, n, n * n))]
    return phi_t, jnp.pad(jnp.stack([alpha, base], axis=1),
                          ((0, WIDE - W), (0, 0)))


def _flat(a):
    return a.reshape(-1, a.shape[-1])


def _block_of(a, n: int) -> int:
    """:func:`block` for n streams shaped as ``a``, (B, S, C)."""
    C = a.shape[-1]
    return block(a.size // C, C, n, a.dtype.itemsize)


def _maps_read_kept(X, phi, alpha, base, statics):
    """-> (``H``, ``u``, what the pass keeps for its backward)."""
    n, shape = len(X), X[0].shape
    with jax.named_scope("mhc_maps"):
        phi_t, coef = _operands(phi, alpha, base, n, X[0].dtype)
    with jax.named_scope("mhc_mix"):
        H, u, kept = _maps_read_call(tuple(_flat(x) for x in X), phi_t, coef,
                                     statics)
    return H.reshape(shape[:-1] + (-1,)), u.reshape(shape), kept


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _maps_read(X, phi, alpha, base, statics):
    H, u, _ = _maps_read_kept(X, phi, alpha, base, statics)
    return H, u, X


def _maps_read_fwd(X, phi, alpha, base, statics):
    H, u, kept = _maps_read_kept(X, phi, alpha, base, statics)
    return (H, u, X), (X, phi, alpha, base, H, kept)


def _maps_read_bwd(statics, saved, cotangents):
    X, phi, alpha, base, H, kept = saved
    dH, du, shares = cotangents
    n, shape, W = len(X), X[0].shape, phi.shape[1]
    with jax.named_scope("mhc_maps"):
        phi_t, coef = _operands(phi, alpha, base, n, X[0].dtype)
    with jax.named_scope("mhc_mix"):
        *dX, dphi_t, dcoef = _read_back_call(
            _flat(du), tuple(_flat(x) for x in X),
            tuple(_flat(s) for s in shares), _flat(H), _flat(dH), kept,
            phi_t, coef, statics)
    with jax.named_scope("mhc_maps"):
        dcoef = jnp.sum(dcoef, axis=-1)[:, :W]
        edges = np.cumsum([0, n, n, n * n])
        dalpha = jnp.stack([jnp.sum(dcoef[0, a:b])
                            for a, b in zip(edges[:-1], edges[1:])])
        dphi = dphi_t[:, :W].transpose(0, 2, 1).reshape(phi.shape)
    return (tuple(d.reshape(shape) for d in dX), dphi.astype(phi.dtype),
            dalpha.astype(alpha.dtype), dcoef[1].astype(base.dtype))


_maps_read.defvjp(_maps_read_fwd, _maps_read_bwd)


def maps_read(X, hc, config):
    """``streams.maps`` and ``streams.read`` in one pass over the streams
    ``X``, n arrays (B, S, C); ``hc`` the sub-layer's row of the stack ->
    (``H`` (B, S, n^2 + 2n) float32, ``u`` (B, S, C), the streams for the
    write to read: the same arrays, whose cotangent comes back to this
    pair's backward as the write's share)."""
    return _maps_read(
        tuple(X), hc["phi"], hc["alpha"], hc["base"],
        (_block_of(X[0], len(X)), hc["base"].shape[0], int(config.hc_sinkhorn_iters),
         float(config.hc_eps), tuple(float(c) for c in config.hc_clamp),
         float(config.rms_eps)))


@jax.custom_vjp
def write(X, y, H):
    """``streams.write``: ``H_res X + H_post^T y``, the n new streams."""
    with jax.named_scope("mhc_mix"):
        new = _write_call(tuple(_flat(x) for x in X), _flat(y), _flat(H),
                          rows=_block_of(y, len(X)))
    return tuple(x.reshape(y.shape) for x in new)


def _write_fwd(X, y, H):
    return write(X, y, H), (X, y, H)


def _write_bwd(saved, G):
    X, y, H = saved
    with jax.named_scope("mhc_mix"):
        dy, *rest = _write_back_call(
            tuple(_flat(g) for g in G), tuple(_flat(x) for x in X), _flat(y),
            _flat(H), rows=_block_of(y, len(X)))
    return (tuple(s.reshape(y.shape) for s in rest[:-1]),
            dy.reshape(y.shape), rest[-1].reshape(H.shape))


write.defvjp(_write_fwd, _write_bwd)
