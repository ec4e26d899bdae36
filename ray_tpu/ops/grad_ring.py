"""``x @ w`` whose weight gradient, under an ``fsdp`` mesh axis, is summed over
the chips while it is being multiplied.

Under ``MeshSpec(fsdp=N)`` every chip holds 1/N of a weight (cut along its
``embed`` axis) and 1/N of the batch.  The partitioner's backward multiplies
the whole ``x^T g`` on every chip from the chip's own tokens and then
reduce-scatters it, and on the TPU that reduce-scatter is a synchronous
instruction: nothing else runs while it does (65 of 690 ms a step for twelve
Mistral-7B layers over four chips, PERF.md PR 30).  What the TPU does overlap
with arithmetic is a ``collective-permute``.  So :func:`dense` is the
decomposition of Wang et al. (ASPLOS 2023, "Overlap communication with
dependent computation via decomposition") by hand, for the backward only: a
``jax.custom_vjp`` around ``x @ w`` whose backward leaves ``dx = g @ w.T`` to
the partitioner (its weight gather is already hidden inside matmuls) and
computes ``dW`` in a ``jax.shard_map`` over ``fsdp`` as N chunk products.  At
step j a chip multiplies its part of the chunk that the chip j + 1 places
along the ring owns, adds the partial sum that arrived from its neighbour
and sends the sum on (``lax.ppermute``) while the next chunk multiplies;
after N - 1 sends each chip holds its own shard summed over all chips.  The
same FLOPs, the same bytes on the wire as the reduce-scatter's ((N - 1)/N of
the matrix a chip) in the same dtype (the compute dtype, as the
partitioner's ``all-reduce-scatter`` has it), each chunk accumulated in
float32 by the matmul unit as before; only the order of an N-term sum
differs.  The whole ``(K, N_out)`` gradient is never materialised.

**Two halves, two ways.**  A chunk travels as two halves of its rows, one
each way round the ring, and the ring visits the chips in the order of their
coordinates (:func:`ring_order`): each half then has a link of its own, and
a hop takes half the time.  On four v5e chips one way round left 2.7 ms a
layer of sends with nothing behind them (the step 689.7 -> 642-654 ms), both
ways 618 (PERF.md, PR 32).

The next product's operands are tied to the sum being sent
(``lax.optimization_barrier``), in one chain through all 2 N products: they
do not depend on what arrives, and left alone the compiler multiplies all of
them first, holds their results and sends last.

**When it engages** is read off the trace, not set by anyone: the ambient
mesh (``jax.sharding.get_abstract_mesh()``) has an ``fsdp`` axis larger than
one and ``x``'s leading (batch) dimension divides over the mesh's batch axes
(``data`` x ``fsdp``).  Otherwise, and on one device, :func:`dense` *is*
``x @ w``: no ``custom_vjp``, no ``shard_map``, the program is what it was.
Only ``fsdp`` is manual inside the ``shard_map``; ``data``, ``tensor`` and
``seq`` stay with the partitioner (under ``data=2,fsdp=2`` the ring runs over
``fsdp`` and the sum over ``data`` is XLA's all-reduce of each chunk).

**What it assumes.**  Which axis of ``w`` is ``embed`` comes from the call
site (the model's ``logical_axes``), and the weights are taken to lie as the
default rules put them (``embed`` over ``fsdp``, the batch over ``data`` then
``fsdp``), as ``ops/remat.py`` assumes.  Under other rules the ``shard_map``
reshards its operands and its result: the answer is the same, only slower.

**What it notes.**  Every ``dW`` traced through the ring is counted for the
first-call record (``util/first_call.py``: ``grad_ring_products`` and
``grad_ring_axis``; ``TrainStep`` starts both at 0, :data:`NO_RINGS`, which
is what they stay where the ring is not engaged).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.util import first_call

AXIS = "fsdp"
#: the mesh axes the batch is cut over, outermost first
#: (``parallel.mesh.DEFAULT_RULES["batch"]``)
_BATCH_AXES = ("data", AXIS)
#: which way round the ring each half of a chunk is sent
_WAY = (-1, 1)


def ring_order(ring: int) -> List[int]:
    """The places of the ``fsdp`` axis in the order the ring visits them.
    Where the ambient mesh's devices say where they sit (a TPU's ``coords``)
    the ring follows :func:`snake` through them, so that on a torus (or a
    grid two chips wide) every hop is between neighbours and the two ways
    round use different links; devices that say nothing (the CPU) are taken
    in mesh order.  On the 2x2 of a v5e host, mesh order crosses the square
    diagonally twice: one way round it is as fast as the snake (0.64 ms for
    29 MB either way), both ways round it gain nothing and along the snake
    they halve the hop (0.32 ms; PERF.md, PR 32)."""
    from jax._src import mesh as mesh_lib  # jax 0.9 has no public accessor
    #                                        that works while tracing

    mesh = mesh_lib.get_concrete_mesh()
    if mesh.empty or AXIS not in mesh.axis_names:
        return list(range(ring))
    along = np.moveaxis(mesh.devices, mesh.axis_names.index(AXIS), -1)
    coords = [getattr(d, "coords", None) for d in along.reshape(-1, ring)[0]]
    if any(c is None for c in coords):
        return list(range(ring))
    return snake([tuple(c) for c in coords])


def snake(coords: Sequence[Tuple[int, ...]]) -> List[int]:
    """Indices of ``coords`` (grid points, fastest dimension first) in
    boustrophedon order: along the first dimension, back along it in the
    next row, and so on up the dimensions, so that consecutive points are
    neighbours wherever the points fill a box."""
    def key(c):
        out, flips = [], 0
        for dim in reversed(range(len(c))):
            rank = sorted({p[dim] for p in coords})
            v = rank.index(c[dim])
            out.append(len(rank) - 1 - v if flips % 2 else v)
            flips += v
        return out
    return sorted(range(len(coords)), key=lambda i: key(coords[i]))


#: the first-call record of a step in which no weight gradient was traced as
#: a ring (a scanned layer's are traced once, whatever the depth)
NO_RINGS = {"grad_ring_products": 0, "grad_ring_axis": 0}


def _shards(batch: int) -> Optional[Tuple[int, int]]:
    """(``data``, ``fsdp``) sizes of the ambient mesh if the ring engages
    for a batch of ``batch`` rows, else ``None``."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.shape.get(AXIS, 1) <= 1:
        return None
    data, ring = (mesh.shape.get(a, 1) for a in _BATCH_AXES)
    return (data, ring) if batch % (data * ring) == 0 else None


def dense(x, w, embed: int):
    """``x @ w`` for ``x`` (batch, ..., K) and ``w`` (K, N_out) whose axis
    ``embed`` (0 or 1) is the one the layout cuts over ``fsdp``.  Under an
    ambient mesh with ``fsdp`` > 1 the weight's gradient is the ring of the
    module text; anywhere else this is the plain product."""
    shards = _shards(x.shape[0]) if x.ndim >= 2 else None
    if shards is None:
        return x @ w
    if w.shape[embed] % shards[1]:
        raise ValueError(
            f"grad_ring.dense: the mesh's {AXIS} axis ({shards[1]}) must "
            f"divide the weight's embed axis (axis {embed} of {w.shape}): "
            "each chip owns an equal slice of the gradient")
    return _ring_dense(x, w, embed)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ring_dense(x, w, embed):
    return x @ w


def _ring_dense_fwd(x, w, embed):
    return x @ w, (x, w)


def _ring_dense_bwd(embed, saved, g):
    x, w = saved
    return g @ w.T, _ring_dw(x, g, embed).astype(w.dtype)


_ring_dense.defvjp(_ring_dense_fwd, _ring_dense_bwd)


def _ring_dw(x, g, embed: int):
    """``x^T g`` summed over every token of the job, cut over ``fsdp`` along
    axis ``embed``: (K, N_out) for ``x`` (batch, ..., K), ``g`` (batch, ...,
    N_out)."""
    data, ring = _shards(x.shape[0])
    first_call.count("grad_ring_products")
    first_call.note(grad_ring_axis=ring)
    # The batch lies data-major over (data, fsdp): give each its own
    # dimension, so that cutting the second over the ring moves nothing.
    x, g = (a.reshape(data, ring, -1, *a.shape[1:]) for a in (x, g))
    tokens = tuple(range(x.ndim - 1))
    # dW's axis ``embed`` is cut into the chips' chunks, ``width`` wide and
    # ``tall`` rows high; a chunk travels as two halves of its rows, one
    # each way round the ring (whole and one way, if its rows are odd).
    width = (x if embed == 0 else g).shape[-1] // ring
    tall = width if embed == 0 else x.shape[-1]
    halves = 2 if tall % 2 == 0 else 1
    piece = tall // halves
    order = ring_order(ring)
    # begins[place in the mesh][half][j]: where along the cut axis the piece
    # begins that a chip multiplies at step j: half ``half`` of the chunk
    # owned j + 1 places further along that half's way round the ring.  Worked
    # out here, not in traced arithmetic: the index sums were two thirds of
    # the step's jaxpr, and tracing them a fifth of the step's first call.
    begins = np.array([[[
        order[(order.index(me) - _WAY[half] * (j + 1)) % ring] * width
        + (half * piece if embed == 0 else 0)
        for j in range(ring)] for half in range(halves)]
        for me in range(ring)], np.uint32)  # unsigned: no wrap-around to trace

    def local(x, g):
        # (numpy into lax: a constant of the jaxpr; ``jnp.asarray`` would
        # put an array on the chip while tracing, 3.6 s of the first call)
        mine = lax.dynamic_index_in_dim(begins, lax.axis_index(AXIS),
                                        keepdims=False)

        def product(j, half, arrived):
            """This chip's part of half ``half`` of the chunk that is
            ``j + 1`` places further along the half's way round the ring,
            plus what arrived, in the operands' dtype."""
            begin = mine[half, j]
            if embed == 0:
                xs = lax.dynamic_slice_in_dim(x, begin, piece, axis=-1)
                gs = g
            else:
                xs = lax.slice_in_dim(x, half * piece, (half + 1) * piece,
                                      axis=-1)
                gs = lax.dynamic_slice_in_dim(g, begin, width, axis=-1)
            if arrived is None:
                return lax.dot_general(xs, gs, ((tokens, tokens), ((), ())))
            part = lax.dot_general(xs, gs, ((tokens, tokens), ((), ())),
                                   preferred_element_type=jnp.float32)
            return (part + arrived.astype(jnp.float32)).astype(x.dtype)

        sums = [None] * halves
        for j in range(ring):
            for half in range(halves):
                acc = product(j, half, sums[half])
                if j < ring - 1:
                    # One chain through every product: the next one's
                    # operands are tied to the sum being sent, or the
                    # compiler multiplies every chunk first and sends last.
                    x, g, acc = lax.optimization_barrier((x, g, acc))
                    acc = lax.ppermute(acc, AXIS, [
                        (order[i], order[(i + _WAY[half]) % ring])
                        for i in range(ring)])
                sums[half] = acc
        return jnp.concatenate(sums)

    P = jax.sharding.PartitionSpec
    operand = P(None, AXIS)
    return jax.shard_map(
        local, in_specs=(operand, operand),
        out_specs=P(AXIS, None) if embed == 0 else P(None, AXIS),
        axis_names={AXIS}, check_vma=False)(x, g)
