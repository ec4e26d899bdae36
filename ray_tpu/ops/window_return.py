"""A window's rows back to their tokens (``models/moe.py:_from_window``) as
one Pallas (Mosaic) kernel that reads the window's R rows once and writes the
N tokens once, where the XLA form gathers all k x N slots of every token,
writes them out as (k, N, D) and reads them again to sum them: six to eight
times the bytes the work has (PERF.md, PR 57).

**In token order a tile's rows are one run.**  The window's rows lie in
expert order.  Sorted by their token (a sort of R keys, the rows outside the
run last, and one gather of R rows, both XLA's), the rows of a tile of T
tokens are one contiguous run, and the kernel places a run's rows with a
product: a (T x P) one-hot of the tokens of P rows times the (P x D) rows on
the matrix unit, accumulated in float32: exact (the products are with 1 and
0; a token's rows, k at most, meet in the float32 sum) and rounded once to
the rows' dtype when the tile leaves.  Rows outside the run sort last, and
the kernel zeroes them by a select in the chunk on which the run ends and
past it, whatever they hold (0 x NaN is NaN; by the same product a row of
the run that is not finite reaches the other tokens of its tile, where the
gather kept it to its own).

**The grid is a list of (tile, chunk) items**, as the grouped products' is a
list of (group, tile) visits: the sorted rows are cut into chunks of P, a
tile visits the chunks its run touches (one, where it has none: every tile
is written), and the list is N / T + R / P items long at most, whatever the
router did; what is left of it repeats the last item and does nothing.  Two
items that follow each other on one chunk (a chunk that ends one tile's run
and begins the next's) fetch it once.  The lists are made in XLA from the
run's bounds, traced scalars inside the windows' loop, and reach the kernel
as scalar prefetch, never as shapes.  Columns are walked in blocks where a
tile's float32 accumulator would pass :data:`ACC_BYTES`.

**Where it runs** (:func:`path`): on the chip, with no mesh or a mesh of one
device, D of whole lane tiles, N a multiple of the token tile and R of the
chunk (:func:`tile`); the gather and its sum everywhere else.  Not a TPU:
Pallas' interpret mode, which only a test asks for (:func:`on_chip`).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LANES = 128
#: rows of a chunk: the contraction of one pass of the matrix unit
CHUNK = 128
#: the most a tile's float32 accumulator may take of the 16 MiB of scoped
#: VMEM (beside it: the tile's output block and the chunk's, in two buffers
#: each, and the product's result)
ACC_BYTES = 2 << 20
_F32 = jnp.float32


def on_chip() -> bool:
    """Whether the kernel is a program of this backend.  Off the chip the
    gather is the return; a test that wants the interpreter's run of the
    kernel replaces this function."""
    return jax.default_backend() == "tpu"


def tile(R: int, N: int, D: int) -> Optional[Tuple[int, int]]:
    """-> (the tokens of a tile, the columns of a block) for a window of R
    rows over N tokens of width D, or None where the kernel's blocks do not
    divide them: 256 tokens (the sweep read tiles of 64 to 256 within a
    twentieth of each other at the six cells' shapes, 512 tokens up to a
    tenth behind and chunks of 256 rows 5-25 % behind chunks of 128,
    ``scripts/moe_return_sweep.py``), fewer where N has fewer; the widest
    block of whole lane tiles that divides D and keeps the accumulator
    inside :data:`ACC_BYTES`."""
    tokens = next((t for t in (256, 128, 64, 32, 16, 8) if N % t == 0), None)
    if tokens is None or R % CHUNK or D % LANES:
        return None
    lanes = D // LANES
    block = max(b for b in range(1, lanes + 1)
                if lanes % b == 0
                and (b == 1 or tokens * b * LANES * 4 <= ACC_BYTES))
    return tokens, block * LANES


def path(R: int, N: int, D: int, mesh) -> str:
    """-> ``"kernel"`` or ``"gather"``: which form the return of a window of
    R rows to N tokens of width D takes under ``mesh`` (the module's
    docstring has the rule)."""
    if on_chip() and (mesh.empty or mesh.size == 1) and tile(R, N, D):
        return "kernel"
    return "gather"  # the backend, a mesh, or the shapes


def items(tokens_sorted, N: int, T: int, P: int):
    """tokens_sorted: (R,) int32, ascending, N where a row is outside the
    run.  -> (the tile of each item, its chunk, (1,) how many items there
    are): N / T + R / P entries each, the tiles in order and each tile's
    chunks in order; the entries past the count repeat the last item."""
    tiles, chunks = N // T, tokens_sorted.shape[0] // P
    # the rows before each tile's first token: compared, not searched (a
    # search is a loop of log R dependent steps, each a program of its own)
    before = jnp.sum(tokens_sorted[None, :]
                     < (np.arange(tiles + 1, dtype=np.int32) * T)[:, None],
                     axis=1, dtype=jnp.int32)
    lo = jnp.minimum(before[:-1] // P, chunks - 1)
    hi = jnp.maximum((before[1:] - 1) // P, lo)
    ends = jnp.cumsum(hi - lo + 1)
    at = np.arange(tiles + chunks, dtype=np.int32)
    item_tile = jnp.minimum(
        jnp.sum(ends[None, :] <= at[:, None], axis=1, dtype=jnp.int32),
        tiles - 1)
    # a tile's first chunk less its first item: read at each item's tile
    # as a select and a sum, as ``moe._scores_at`` reads its scores
    shift = jnp.sum(jnp.where(
        item_tile[:, None] == np.arange(tiles, dtype=np.int32)[None, :],
        (lo - (ends - (hi - lo + 1)))[None, :], 0), axis=1)
    return (item_tile, jnp.minimum(at, ends[-1] - 1) + shift,
            ends[-1:])


def _kernel(item_tile, item_chunk, count, inside, tokens_ref, rows_ref,
            out_ref, acc_ref, *, T: int, P: int):
    """A grid step: item ``w`` of the list, over one block of columns."""
    from jax.experimental import pallas as pl

    w = pl.program_id(1)
    here, chunk = item_tile[w], item_chunk[w]
    opens = (w == 0) | (item_tile[jnp.maximum(w - 1, 0)] != here)
    closes = (w == count[0] - 1) | (
        item_tile[jnp.minimum(w + 1, pl.num_programs(1) - 1)] != here)
    # the chunk's rows that lie in the run: all of them but in the chunk on
    # which the run ends and in those past it, which an empty tile is sent to
    own = inside[0] - chunk * P

    @pl.when(opens)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(w < count[0])
    def _():
        def cut():
            # what a row outside the run holds is left out by a select, not
            # by the one-hot's zero: 0 x NaN is NaN
            rows = rows_ref[...]
            at = lax.broadcasted_iota(jnp.int32, rows.shape, 0)
            return jnp.where(at < own, rows.astype(_F32), 0).astype(rows.dtype)

        rows = lax.cond(own >= P, lambda: rows_ref[...], cut)
        local = tokens_ref[pl.ds(chunk, 1), :] - here * T         # (1, P)
        hot = (lax.broadcasted_iota(jnp.int32, (T, P), 0)
               == local).astype(rows.dtype)
        acc_ref[...] += jnp.dot(
            hot, rows, preferred_element_type=_F32,
            precision=(lax.Precision.HIGHEST if rows.dtype == _F32 else None))

        @pl.when(closes)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def call(rows_sorted, tokens_sorted, inside, N: int, T: int, block: int,
         chunk: int, interpret: bool):
    """The ``pallas_call``.  rows_sorted: (R, D), the window's rows in token
    order, those of the run first; tokens_sorted: (R,) int32, their tokens,
    N where a row is outside the run; ``inside``: (1,) int32, the rows of
    the run.  -> (N, D) in the rows' dtype, each token the float32 sum of
    its rows.  ``T`` tokens a tile, ``block`` columns a grid step
    (:func:`tile` is the rule), ``chunk`` rows an item."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, D = rows_sorted.shape
    P = chunk
    lists = items(tokens_sorted, N, T, P)
    return pl.pallas_call(
        functools.partial(_kernel, T=T, P=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(D // block, N // T + R // P),
            in_specs=[
                # every row's token, whole and fetched once
                pl.BlockSpec((R // P, P), lambda d, w, *_: (0, 0)),
                pl.BlockSpec((P, block),
                             lambda d, w, tiles, chunks, *_: (chunks[w], d)),
            ],
            out_specs=pl.BlockSpec((T, block),
                                   lambda d, w, tiles, *_: (tiles[w], d)),
            scratch_shapes=[pltpu.VMEM((T, block), _F32)]),
        out_shape=jax.ShapeDtypeStruct((N, D), rows_sorted.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_window_return",
    )(*lists, inside, tokens_sorted.reshape(R // P, P), rows_sorted)


def by_token(pairs, inverse, run):
    """A window's rows in token order.  -> (their tokens, (R,) int32
    ascending, N where a row is outside the run: those sort last; the place
    in the window of each)."""
    first, stop, lead = run
    N, k = inverse.shape
    at = lax.iota(jnp.int32, pairs.shape[0])
    inside = (at >= lead) & (at < lead + (stop - first))
    return lax.sort((jnp.where(inside, pairs // k, N), at), num_keys=1)


@functools.partial(jax.jit,
                   static_argnames=("T", "block", "chunk", "interpret"))
def _sorted_and_placed(rows, pairs, inverse, run, *, T, block, chunk,
                       interpret):
    """Jitted, so that a step traces and lowers it once for all its layers
    and both its passes and not once for each (twelve in ``lfm2-ep4-s8192``:
    2.4 s of a first call on the chip machine's host; PERF.md, PR 57); each
    call keeps its caller's scope in the compiled step's names.  What the
    trace reads of the module and the backend comes in as static arguments:
    another tile, or the interpreter, is another trace."""
    first, stop, _ = run
    tokens_sorted, source = by_token(pairs, inverse, run)
    return call(rows[source], tokens_sorted, (stop - first)[None],
                inverse.shape[0], T, block, chunk, interpret)


def from_window(rows, pairs, inverse, run):
    """``models/moe.py:_from_window`` by the kernel: the same arguments, the
    same result to the order in which a token's rows are added."""
    T, block = tile(rows.shape[0], inverse.shape[0], rows.shape[1])
    return _sorted_and_placed(
        rows, pairs, inverse, run, T=T, block=block, chunk=CHUNK,
        interpret=jax.default_backend() != "tpu")
