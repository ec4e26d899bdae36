"""The short depthwise causal convolution of ``models/mamba2.py:causal_conv``
with what surrounds it at its call sites, as one Pallas (Mosaic) pass a
direction: each input array is read once from HBM, the K - 1 earlier
(backward: later) positions a block needs come through a second block map of
one sublane tile, the arithmetic is float32 in VMEM and the result is rounded
once on the way out.  XLA's form pads the input, reads K shifted float32
slices of the padded copy and writes a float32 sum that the caller's
activation reads again; its backward does the same the other way and K
reductions more for the taps (PERF.md, PR 61).

**Two forms, one body.**  *Plain* (:func:`conv`, kinds `M`, `K`, `G`):
``act(b + sum_k w_k x_{t-(K-1)+k})``, ``act`` silu or nothing, ``b`` or
none, over a span of x's columns read in place (``offset``, ``widths``: the
state-space mixer's ``xBC`` is columns 4096:10240 of its projection's
output, and ``xs | B | C`` leave as arrays of their own, a call each).
*Gated* (:func:`gated`, kind `C`): ``C * conv(B * u)`` over ``[B | C | u]``
as the projection writes it.  Both walk a block in chunks of :data:`ROWS`
positions; a chunk's shifted copies are, a sublane tile at a time, a select
against the tile before and a rotation (``_tiles``), so nothing is loaded at
an unaligned row.  A chunk's arithmetic is traced once a signature and its
equations bound again for every chunk a kernel's text writes out
(``_chunks``); a turn of the in-kernel loop holds as many chunks as fill
:data:`TURN` lanes, since a turn of one chunk waits for its own loads.

**The backward is one pass the other way.**  It reads x (or ``[B | C | u]``)
and dy, recomputes the pre-activation (K multiply-adds a value: the
residuals stay ``(x, w, b)``), and writes dx (the gated form: the whole
``[dB | dC | du]``) in x's dtype.  The taps' and the bias's gradients are
float32 sums over :data:`ROWS` rows a channel, kept in VMEM across the grid's
two sequential axes (rows, blocks of positions) and reduced when a block of
channels ends: one (8, width) float32 array, taps first, the bias behind them.

**Blocks** (:func:`blocks`): the plain form's grid walks the span in blocks
of :data:`LANES_STEP` lanes (a width that is no multiple of 128, as 2880,
ends in a ragged block: nothing is padded in HBM; a span inside a wider
array needs a block that divides its offset and every width), the gated
form's block is the array's whole width, walked inside the kernel; the
positions of a block are the most that keep a step's blocks inside
:data:`STEP_BYTES`.

**Where it runs** (:func:`path`): on the chip, with no mesh or a mesh of one
device, S a whole number of chunks, a width :func:`blocks` accepts, at most
seven taps; ``causal_conv`` and the call sites' own expressions everywhere
else (the CPU, the tests' oracle, a mesh).  Not a TPU: Pallas' interpret
mode, which only a test asks for (:func:`on_chip`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.util import first_call

LANES = 128
#: positions a chunk of the in-kernel loop, and of a block's neighbouring
#: rows: one packed bf16 sublane tile
ROWS = 16
#: a sublane tile of float32
TILE = 8
#: lanes of a grid step (plain); a walk inside one (gated) takes twice as
#: many where the width allows, for half the walks in the kernel's text
LANES_STEP = 512
#: the most a step's blocks, in and out, may hold (each in two buffers
#: inside the 16 MiB of scoped VMEM)
STEP_BYTES = 4 << 20
#: lanes x chunks of a turn of the in-kernel loop, whose chunks are written
#: out: a turn of one chunk waits for its own loads and converts, and every
#: chunk written out is traced, lowered and compiled in a step's first call
TURN = 4 * 512
_F32 = jnp.float32


def on_chip() -> bool:
    """Whether the kernel is a program of this backend.  Off the chip XLA's
    form is the convolution; a test that wants the interpreter's run of the
    kernel replaces this function."""
    return jax.default_backend() == "tpu"


def blocks(S: int, full: int, offset: int, widths: Sequence[int],
           itemsize: int, gated: bool = False) -> Optional[Tuple[int, int]]:
    """-> (positions, lanes) of a grid step over columns ``offset`` and on of
    a (rows, S, ``full``) array, cut into calls of ``widths``, ``itemsize``
    the wider of x's and the result's; for the gated form ``full`` is three
    times the one width, and the lanes are those of a walk inside the step.
    None where no block does (the module's docstring has the rule)."""
    if S % ROWS:
        return None
    inside = offset or sum(widths) != full or len(widths) > 1
    if gated or inside:
        shared = math.gcd(offset, *widths)
        lanes = next((n for n in (LANES_STEP * (1 + gated), LANES_STEP, 256,
                                  LANES) if shared % n == 0), None)
    else:  # the whole array: ragged at the end, or the array's own width
        lanes = LANES_STEP if full >= LANES_STEP else full
    if lanes is None:
        return None
    # the backward's blocks: x, dy and dx ([B | C | u], dy and its three)
    step = (7 * widths[0] if gated else 3 * lanes) * itemsize
    rows = next((r for r in (2048, 1024, 512, 256, 128, 64, 32, ROWS)
                 if S % r == 0 and r * step <= STEP_BYTES), None)
    return rows and (rows, lanes)


def path(shape, taps: int, mesh, offset: int = 0, widths=None,
         gated: bool = False) -> str:
    """-> ``"kernel"`` or ``"xla"``: which form a convolution of ``taps``
    taps over a (rows, S, full) array takes under ``mesh``."""
    _, S, full = shape
    widths = widths or ((full // 3,) if gated else (full - offset,))
    if (on_chip() and (mesh.empty or mesh.size == 1) and 1 < taps < 8
            and blocks(S, full, offset, widths, 4, gated)):
        return "kernel"
    return "xla"  # the backend, a mesh, or the shapes


def engaged(shape, taps: int, offset: int = 0, widths=None,
            gated: bool = False) -> bool:
    """From a call site, while its layer is traced: whether this call is
    the kernel's, here and now; noted in the first-call record."""
    kernel = path(shape, taps, jax.sharding.get_abstract_mesh(), offset,
                  widths, gated) == "kernel"
    first_call.note(conv_kernel="kernel" if kernel else "xla")
    first_call.count("conv_calls")
    return kernel


def _tiles(parts, j: int, late: bool):
    """``parts``: three neighbouring sublane tiles of float32.  -> the two
    tiles ``[i] = joined[i + 8 - j]`` (``late``: ``joined[i + j]``) of their
    run: a tile at a time one select against its neighbour and one
    rotation (a roll of a whole chunk costs a select a tile more)."""
    from jax.experimental.pallas import tpu as pltpu

    row = lax.broadcasted_iota(jnp.int32, parts[0].shape, 0)
    if late:
        return jnp.concatenate(
            [pltpu.roll(lax.select(row < j, parts[i + 1], parts[i]),
                        TILE - j, 0) for i in range(2)], axis=0)
    return jnp.concatenate(
        [pltpu.roll(lax.select(row >= TILE - j, parts[i], parts[i + 1]), j, 0)
         for i in range(2)], axis=0)


@functools.lru_cache(maxsize=None)
def _chunks(taps: int, act: bool, bias: bool, gated: bool):
    """The arithmetic of one chunk of :data:`ROWS` positions over some
    lanes, as three functions of arrays that are traced once a signature and
    bound again at every further chunk a kernel's text holds
    (``gdn_kernel._traced_once``: a kernel's body written out chunk by
    chunk through ``jnp`` is traced op by op, seconds of a step's first
    call).  ``x``: the chunk as stored, (x,) or (B, C, u); ``tail``: the
    last sublane tile of the convolution's input before the chunk, float32;
    ``w``: (taps, lanes) and ``b``: (1, lanes) float32, or None.

    ``forward(x, tail, w, b) -> (the result, float32; the chunk's tail)``.
    ``cotangent(x, dy, tail, w, b) -> (g, the pre-activation's cotangent;
    the chunk's tail; its terms of the taps' and the bias's gradients,
    (ROWS, lanes) each; gated: C's cotangent)``.  ``pull(x, g, head, w) ->
    x's cotangents, float32``: ``head`` the first tile of the next chunk's
    g.  ``tail(x)``: the tail of a chunk by itself, untraced."""
    from ray_tpu.ops.gdn_kernel import _traced_once

    def source(x):
        x = [a.astype(_F32) for a in x]
        return x[0] * x[2] if gated else x[0]

    def pre(z, tail, w, b):
        parts = (tail, z[:TILE], z[TILE:])
        shifted = [_tiles(parts, taps - 1 - k, False)
                   for k in range(taps - 1)] + [z]
        y = shifted[0] * w[0:1]
        for k in range(1, taps):
            y = y + shifted[k] * w[k:k + 1]
        return (y + b if bias else y), shifted

    @_traced_once
    def forward(x, tail, w, b):
        z = source(x)
        y, _ = pre(z, tail, w, b)
        if gated:
            y = x[1].astype(_F32) * y
        elif act:
            y = y * jax.nn.sigmoid(y)
        return y, z[TILE:]

    @_traced_once
    def cotangent(x, dy, tail, w, b):
        z = source(x)
        y, shifted = pre(z, tail, w, b)
        dy = dy.astype(_F32)
        if gated:
            g = dy * x[1].astype(_F32)
        elif act:
            s = jax.nn.sigmoid(y)
            g = dy * (s * (1.0 + y * (1.0 - s)))
        else:
            g = dy
        terms = [g * part for part in shifted] + ([g] if bias else [])
        return (g, z[TILE:], terms) + ((dy * y,) if gated else ())

    @_traced_once
    def pull(x, g, head, w):
        parts = (g[:TILE], g[TILE:], head)
        dz = _tiles(parts, taps - 1, True) * w[0:1]
        for k in range(1, taps - 1):
            dz = dz + _tiles(parts, taps - 1 - k, True) * w[k:k + 1]
        dz = dz + g * w[taps - 1:taps]
        if gated:
            return dz * x[2].astype(_F32), dz * x[0].astype(_F32)
        return (dz,)

    return forward, cotangent, pull, lambda x: source(x)[TILE:]


def _turns(count: int, lanes: int, step, carry):
    """``carry = step(c, carry)`` for c in ``range(count)``: a loop of whole
    turns of :data:`TURN` / ``lanes`` steps (8 at most), the rest written
    out."""
    unroll = max(1, min(8, TURN // lanes))
    turns, rest = divmod(count, unroll)

    def turn(t, carry):
        for u in range(unroll):
            carry = step(t * unroll + u, carry)
        return carry

    carry = lax.fori_loop(0, turns, turn, carry) if turns else carry
    for u in range(rest):
        carry = step(turns * unroll + u, carry)
    return carry


def _walks(lanes: int, width: int, gated: bool):
    """The static column slices a step walks: (of x's block: the
    convolution's input, or B, C and u; of everything one width wide)."""
    if not gated:
        return [((slice(0, lanes),), slice(0, lanes))]
    return [(tuple(slice(g * width + at, g * width + at + lanes)
                   for g in range(3)), slice(at, at + lanes))
            for at in range(0, width, lanes)]


def _rows_at(c):
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(c * ROWS, ROWS), ROWS)


def _forward(at_ref, *refs, taps: int, act: bool, bias: bool, gated: bool,
             lanes: int, width: int):
    """A grid step.  refs: the ROWS rows before the block, the block, w,
    (b), the result's block.  (``at_ref``: the span's first block of lanes,
    the block maps'.)"""
    from jax.experimental import pallas as pl

    before_ref, x_ref, w_ref = refs[:3]
    b_ref, out_ref = (refs[3] if bias else None), refs[-1]
    forward, _, _, tail_of = _chunks(taps, act, bias, gated)
    first = jnp.full((TILE, lanes), pl.program_id(2), jnp.int32) == 0
    for cols, one in _walks(lanes, width, gated):
        w = w_ref[:, one].astype(_F32)
        b = b_ref[:, one].astype(_F32) if bias else None

        def chunk(c, tail, cols=cols, one=one, w=w, b=b):
            at = _rows_at(c)
            y, tail = forward([x_ref[0, at, col] for col in cols], tail, w, b)
            out_ref[0, at, one] = y.astype(out_ref.dtype)
            return tail

        tail = tail_of([before_ref[0, :, col] for col in cols])
        _turns(x_ref.shape[1] // ROWS, lanes, chunk,
               jnp.where(first, jnp.zeros_like(tail), tail))


def _backward(at_ref, *refs, taps: int, act: bool, bias: bool, gated: bool,
              lanes: int, width: int):
    """A grid step of the backward.  refs: the ROWS rows of x before the
    block, x's block, the ROWS rows after it, dy's block, the rows after
    it, w, (b); dx's block, the (8, lanes) gradients of the taps and the
    bias; the float32 sums a channel, (taps + 1, ROWS, lanes).  A turn of
    the loop finds a chunk's g and, with its first tile, writes the chunk
    before's dx (at the block's first chunk into that chunk's own place,
    which the next turn writes again); the last chunk's waits for the rows
    after the block."""
    from jax.experimental import pallas as pl

    before_ref, x_ref, after_ref, dy_ref, dy_after_ref, w_ref = refs[:6]
    b_ref = refs[6] if bias else None
    dx_ref, dwb_ref, sums_ref = refs[-3:]
    _, cotangent, pull, tail_of = _chunks(taps, act, bias, gated)
    s, ends = pl.program_id(2), pl.num_programs(2) - 1
    opens = (s == 0) & (pl.program_id(1) == 0)
    closes = (s == ends) & (pl.program_id(1) == pl.num_programs(1) - 1)
    first = jnp.full((TILE, lanes), s, jnp.int32) == 0
    last = jnp.full((TILE, lanes), s, jnp.int32) == ends
    chunks = x_ref.shape[1] // ROWS

    @pl.when(opens)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    for cols, one in _walks(lanes, width, gated):
        w = w_ref[:, one].astype(_F32)
        b = b_ref[:, one].astype(_F32) if bias else None
        into = (cols[0], cols[2]) if gated else (one,)  # of dx's block

        def write(at, x, g, head, w=w, into=into):
            for col, dx in zip(into, pull(x, g, head, w)):
                dx_ref[0, at, col] = dx.astype(dx_ref.dtype)

        def chunk(c, carry, cols=cols, one=one, w=w, b=b, write=write):
            tail, g_before, x_before = carry
            at = _rows_at(c)
            x = [x_ref[0, at, col] for col in cols]
            g, tail, terms, *dC = cotangent(x, dy_ref[0, at, one], tail, w, b)
            for k, term in enumerate(terms):
                sums_ref[k, :, one] += term
            if gated:
                dx_ref[0, at, cols[1]] = dC[0].astype(dx_ref.dtype)
            write(_rows_at(jnp.maximum(c - 1, 0)), x_before, g_before,
                  g[:TILE])
            return tail, g, x

        zeros = jnp.zeros((TILE, lanes), _F32)
        x_before = [before_ref[0, :, col] for col in cols]
        tail, g, x = _turns(
            chunks, lanes, chunk, (jnp.where(first, zeros, tail_of(x_before)),
                            jnp.zeros((ROWS, lanes), _F32), x_before))
        x_after = [after_ref[0, :, col] for col in cols]
        head = cotangent(x_after, dy_after_ref[0, :, one], tail, w, b)[0]
        write(_rows_at(chunks - 1), x, g, jnp.where(last, zeros, head[:TILE]))

    @pl.when(closes)
    def _():
        for k in range(8):
            if k < taps + bias:
                dwb_ref[k:k + 1, :] = jnp.sum(sums_ref[k], axis=0,
                                              keepdims=True)
            else:
                dwb_ref[k:k + 1, :] = jnp.zeros((1, dwb_ref.shape[1]), _F32)


def call(x, w, b, dy, at, *, act: bool, out_dtype, width: int, gated: bool,
         block: Tuple[int, int]):
    """The ``pallas_call`` of one direction over ``width`` columns of x,
    (rows, S, full), from its block of lanes ``at`` ((1,) int32: a number the
    block maps read, so that spans that differ in their place alone share a
    trace and a compiled kernel).  Forward (``dy`` None): -> the (rows, S,
    width) result in ``out_dtype``.  Backward: -> (dx, the same span in x's
    dtype, or gated the whole ``[dB | dC | du]``; the (8, width) float32
    gradients, a tap a row and the bias's behind them).  ``block``:
    (positions, lanes) a step, :func:`blocks`'."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, S, full = x.shape
    taps, (tS, lanes) = w.shape[0], block
    wide = full if gated else lanes  # of a block of x
    narrow = width if gated else lanes  # of a block one width wide
    tiles, last = tS // ROWS, S // ROWS - 1

    def block_of(kind: str, lanes: int, placed: bool = False):
        """x's (or dy's) block of a step, or the ROWS rows on a side of it
        (at a row's end its own last rows, which the kernel zeroes);
        ``placed``: of the array the span lies in."""
        def positions(s):
            return {"own": s, "before": jnp.maximum(s * tiles - 1, 0),
                    "after": jnp.minimum((s + 1) * tiles, last)}[kind]

        return pl.BlockSpec(
            (1, tS if kind == "own" else ROWS, lanes),
            lambda c, r, s, at: (r, positions(s), c + at[0] * placed))

    def one_row(n: int):
        return pl.BlockSpec((n, narrow), lambda c, r, s, at: (0, c))

    statics = dict(taps=taps, act=act, bias=b is not None, gated=gated,
                   lanes=lanes, width=width)
    operands = [w] + ([b.reshape(1, -1)] if b is not None else [])
    operand_specs = [one_row(taps)] + ([one_row(1)] if b is not None else [])
    grid = (1 if gated else pl.cdiv(width, lanes), rows, S // tS)
    interpret = jax.default_backend() != "tpu"
    if dy is None:
        return pl.pallas_call(
            functools.partial(_forward, **statics),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid,
                in_specs=[block_of("before", wide, True),
                          block_of("own", wide, True)] + operand_specs,
                out_specs=block_of("own", narrow)),
            out_shape=jax.ShapeDtypeStruct((rows, S, width), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=interpret, name="conv_forward",
        )(at, x, x, *operands)
    return pl.pallas_call(
        functools.partial(_backward, **statics),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[block_of("before", wide, True),
                      block_of("own", wide, True),
                      block_of("after", wide, True), block_of("own", narrow),
                      block_of("after", narrow)] + operand_specs,
            out_specs=[block_of("own", wide), one_row(8)],
            scratch_shapes=[pltpu.VMEM((taps + 1, ROWS, narrow), _F32)]),
        out_shape=[jax.ShapeDtypeStruct(
            (rows, S, full if gated else width), x.dtype),
            jax.ShapeDtypeStruct((8, width), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="conv_backward",
    )(at, x, x, x, dy, dy, *operands)


_call = jax.jit(call, static_argnames=("act", "out_dtype", "width", "gated",
                                       "block"))


def _placed(x, w, b, dy, offset: int, width: int, out_dtype, **statics):
    """:func:`call` through its jit, in :func:`blocks`' blocks."""
    result = jnp.dtype(out_dtype) if dy is None else dy.dtype
    block = blocks(x.shape[1], x.shape[2], offset, (width,),
                   max(x.dtype.itemsize, result.itemsize),
                   statics["gated"])
    return _call(x, w, b, dy, jnp.full((1,), offset // block[1], jnp.int32),
                 out_dtype=out_dtype, width=width, block=block, **statics)


def _spans(w, b, offset: int, widths):
    """(the columns of x a call starts at, its width, its taps, its bias) of
    each of ``widths``."""
    at = 0
    for width in widths:
        yield (offset + at, width, w[:, at:at + width],
               None if b is None else b[at:at + width])
        at += width


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _conv(x, w, b, act, out_dtype, offset, widths):
    return tuple(_placed(x, w_, b_, None, at, width, out_dtype, act=act,
                         gated=False)
                 for at, width, w_, b_ in _spans(w, b, offset, widths))


def _conv_fwd(x, w, b, act, out_dtype, offset, widths):
    return _conv(x, w, b, act, out_dtype, offset, widths), (x, w, b)


def _conv_bwd(act, out_dtype, offset, widths, saved, dys):
    x, w, b = saved
    taps = w.shape[0]
    dxs, dwbs = zip(*(
        _placed(x, w_, b_, dy, at, width, None, act=act, gated=False)
        for dy, (at, width, w_, b_) in zip(dys, _spans(w, b, offset, widths))))
    dx = dxs[0] if len(dxs) == 1 else jnp.concatenate(dxs, axis=-1)
    if dx.shape != x.shape:  # the span's place in the whole array
        dx = jnp.pad(dx, ((0, 0), (0, 0),
                          (offset, x.shape[-1] - offset - dx.shape[-1])))
    dwb = dwbs[0] if len(dwbs) == 1 else jnp.concatenate(dwbs, axis=-1)
    return (dx, dwb[:taps].astype(w.dtype),
            None if b is None else dwb[taps].astype(b.dtype))


_conv.defvjp(_conv_fwd, _conv_bwd)


def conv(x, w, b, *, act: bool, out_dtype, offset: int = 0, widths=None):
    """``act(b + sum_k w_k x_{t-(K-1)+k})`` (``act``: silu, else nothing) in
    float32, rounded once to ``out_dtype``, over columns ``offset`` and on of
    x, (rows, S, full); w: (K, width), tap K-1 the position itself; b:
    (width,) or None.  ``widths``: the span leaves as one array a width, a
    tuple (the span is their sum wide); None: one array, to x's last
    column.  Differentiable in x, w and b; the gradients of w and b are
    float32 sums, rounded to their dtypes."""
    outs = _conv(x, w, b, act, jnp.dtype(out_dtype).name, offset,
                 tuple(widths or (x.shape[-1] - offset,)))
    return outs if widths else outs[0]


@jax.custom_vjp
def gated(bcu, w):
    """bcu: (rows, S, 3 x width), ``[B | C | u]``; w: (K, width) ->
    ``C * conv(B * u)``, (rows, S, width) in bcu's dtype: float32
    throughout, rounded once (``models/shortconv.py:gated_conv``)."""
    return _placed(bcu, w, None, None, 0, w.shape[1], bcu.dtype.name,
                   act=False, gated=True)


def _gated_fwd(bcu, w):
    return gated(bcu, w), (bcu, w)


def _gated_bwd(saved, dy):
    bcu, w = saved
    dbcu, dwb = _placed(bcu, w, None, dy, 0, w.shape[1], None, act=False,
                        gated=True)
    return dbcu, dwb[:w.shape[0]].astype(w.dtype)


gated.defvjp(_gated_fwd, _gated_bwd)
