"""Grouped matmul over ragged groups: rows of ``lhs`` sorted into G
contiguous groups, group g multiplied by its own ``rhs[g]``.

    out[start_g : start_g + size_g] = lhs[start_g : start_g + size_g] @ rhs[g]

What a dropless mixture-of-experts layer needs (``models/moe.py``): every
(token, expert) pair is a row, the groups are the experts, their sizes are
data.  Shapes are static; only ``group_sizes`` varies, and an empty group
costs nothing.  ``rhs`` may hold a run of the groups only (a chip's share of
the experts, ``first_group`` onwards): the other groups' rows come out zero
and their tiles are not visited.

One implementation: the Pallas ``megablox`` kernels that ship with jax
(``gmm`` for the forward and for dx, ``tgmm`` for the per-group dW), float32
accumulation, wrapped here in one ``custom_vjp``.  ``jax.lax.ragged_dot`` was
timed against them on the v5e at OLMoE's shapes and lost (PERF.md, PR 26).
The (rows, contraction, columns) tile each call walks is a function of that
call's own shapes (:func:`tile_for`): the kernels mask what of a tile lies
past a width, so a tile that does not fit the width multiplies zeros (1280
walked in tiles of 1024 took 1.5 times what it takes in tiles of 640).
On a TPU the kernels are Mosaic calls; on the CPU, for the tests, the same
kernels run in Pallas interpret mode; any other backend is refused by name.
Under a mesh the caller puts the call inside a ``shard_map`` (a Mosaic call
cannot be partitioned).
"""

from __future__ import annotations

import functools
import math

import jax
import numpy as np

from ray_tpu.util import first_call

#: What the chip sets, not a model: the row tile, the widest tile of a
#: contraction or of the columns, and the lanes a tile's width is a multiple
#: of.  At 512 rows a visit multiplies 512 times for each byte of the
#: expert's matrix it reads, over the v5e's 240 a byte (256 rows sit on the
#: ridge: faster where groups hold a few hundred rows, slower where they
#: hold a thousand, and no shape says which); a (512, 1024, 1024) visit's
#: buffers take 10-12 of the 16 MiB of VMEM a kernel may use, and a width
#: tile of 2048 or a row tile of 1024 beside 1024 x 1024 is refused.  Swept
#: on the v5e at the five expert cells' shapes with
#: ``scripts/gmm_tile_sweep.py`` (PERF.md, PR 50; OLMoE's first in PR 26).
ROW_TILE, WIDEST_TILE, LANES = 512, 1024, 128


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise NotImplementedError(
            f"grouped_matmul runs the Pallas megablox kernels: a TPU, or the "
            f"CPU in interpret mode for tests; backend {backend!r} has "
            f"neither")
    return backend == "cpu"


def _width_tile(width: int) -> int:
    """The tile a width (a contraction or the columns) is walked in: the
    width itself where one tile takes it, else the multiple of 128 whose
    tiles overhang it least, the widest of those (1280 -> 640, 1856 -> 640,
    2688 -> 896; 2048 and 4096 -> 1024)."""
    if width <= WIDEST_TILE:
        return width
    return min(range(LANES, WIDEST_TILE + 1, LANES),
               key=lambda t: (-(-width // t) * t, -t))


def tile_for(m: int, k: int, n: int):
    """The (rows, contraction, columns) tile of a product of (m, k) rows
    with (k, n) matrices, from its shapes alone; the forward, dx (which
    contracts over the forward's columns) and dW all ask here, each with
    its own roles.  The kernels want the row tile to divide the rows.
    Noted in the first-call record as ``gmm_tiles``."""
    chosen = math.gcd(m, ROW_TILE), _width_tile(k), _width_tile(n)
    first_call.entry("gmm_tiles", f"{m}x{k}x{n}", chosen)
    return chosen


def _held(rhs, group_sizes, first_group: int):
    """The kernels' ``group_offset``: None where ``rhs`` holds every group,
    which is the call they always got."""
    if rhs.shape[0] == group_sizes.shape[0]:
        return None
    return np.int32(first_group)  # numpy: a trace-time constant


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(lhs, rhs, group_sizes, first_group: int = 0):
    """lhs: (M, K); rhs: (H, K, N), same dtype, the matrices of groups
    ``first_group .. first_group + H``; group_sizes: (G,) int32 summing to
    M, H <= G.  -> (M, N) in that dtype, accumulated in float32; rows of a
    group ``rhs`` does not hold are zero."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    (m, k), n = lhs.shape, rhs.shape[2]
    return gmm(lhs, rhs, group_sizes, lhs.dtype, tile_for(m, k, n),
               group_offset=_held(rhs, group_sizes, first_group),
               interpret=_interpret())


def _fwd(lhs, rhs, group_sizes, first_group):
    return (grouped_matmul(lhs, rhs, group_sizes, first_group),
            (lhs, rhs, group_sizes))


def _bwd(first_group, res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, rhs, group_sizes = res
    (m, k), n = lhs.shape, rhs.shape[2]
    interpret = _interpret()
    offset = _held(rhs, group_sizes, first_group)
    # dx: the same grouped product against each group's transposed matrix
    dlhs = gmm(g, rhs, group_sizes, lhs.dtype, tile_for(m, n, k),
               group_offset=offset, transpose_rhs=True, interpret=interpret)
    # dW: per group held, its rows of lhs transposed times its rows of g
    drhs = tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                tile_for(m, k, n), group_offset=offset,
                num_actual_groups=rhs.shape[0], interpret=interpret)
    return dlhs, drhs, None


grouped_matmul.defvjp(_fwd, _bwd)
