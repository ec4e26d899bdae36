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

#: (rows, contraction, columns) tile the kernels walk, cut to the operands
#: where they are smaller.  Timed on the v5e at 65536 x 2048 x 1024 in 64
#: groups (PERF.md, PR 26).
TILING = (512, 1024, 1024)


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise NotImplementedError(
            f"grouped_matmul runs the Pallas megablox kernels: a TPU, or the "
            f"CPU in interpret mode for tests; backend {backend!r} has "
            f"neither")
    return backend == "cpu"


def _tiling(m: int, k: int, n: int):
    # the kernels want the row tile to divide the rows
    return math.gcd(m, TILING[0]), min(k, TILING[1]), min(n, TILING[2])


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
    return gmm(lhs, rhs, group_sizes, lhs.dtype, _tiling(m, k, n),
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
    dlhs = gmm(g, rhs, group_sizes, lhs.dtype, _tiling(m, n, k),
               group_offset=offset, transpose_rhs=True, interpret=interpret)
    # dW: per group held, its rows of lhs transposed times its rows of g
    drhs = tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                _tiling(m, k, n), group_offset=offset,
                num_actual_groups=rhs.shape[0], interpret=interpret)
    return dlhs, drhs, None


grouped_matmul.defvjp(_fwd, _bwd)
