"""The rotary pass of ``models/layers.py:rope`` as one Pallas (Mosaic) kernel:
``x * cos + partner(x) * sin`` in float32, one rounding to x's dtype, where
the partner lanes come from a roll along the lanes (the XLU's work) and not
from a product with a permutation.  HBM sees x once in and the result once
out; cos and the signed sin are two (positions, hd) float32 tables the
caller makes once a pass, so the kernel has no branch on a model: which
lanes rotate, over which frequencies, scaled by what and in which direction
all live in the tables, and the pairing in two numbers (``half``, the
distance to a lane's partner, and ``before``, the lanes ahead of the rotary
part).  An array may also leave multiplied by a constant, in float32 before
the one rounding (``scales``: the splash call's scale of q, which after a
Mosaic call would be an XLA pass of its own over q, forward and backward).

**One call rotates every array of a layer that shares the tables** (q and k:
two inputs, two outputs), over a grid of (row, block of positions, group of
heads).  A grid step holds ``block`` positions of a group's heads of every
array; the tables' block depends on the positions alone, so the pipeline
fetches it once for all of a block's groups.  Inside a step a loop walks the
block in chunks of a few sublane tiles and every head of the chunk reads the
chunk's cos and sin from registers.  The heads of a step are unrolled in the
kernel's text, which is why they are walked in groups: a step of all 80
heads of a window layer ran as fast and took the compiler 1.2 s a call, 18 s
of a step's first call (PERF.md, PR 53).

**Layouts.**  The forward reads (rows, positions, heads x hd), which is what
the projections write, a head a run of whole lane tiles, and writes (rows,
heads, positions, hd), which is what the splash call reads: the transpose
costs the kernel nothing (a head's lanes are sliced out of the block and
stored under the head's index), and XLA's transposes around the call fold
into it.  The backward is the same kernel with the sine negated (the caller's
tables), read head-major as the splash call's backward writes and written
position-major as the projections' transposes read.

**Where it runs** (:func:`path`): heads of whole lane tiles (``hd % 128 ==
0``), the rotate-half pairing, positions a block divides, on the chip, and
with no mesh or a mesh of one device; the product of ``models/layers.py``
everywhere else.  (Under a larger mesh the call would sit in
``ops/placement.py``'s ``shard_map``, rows over `data` / `fsdp` and heads
over `tensor`, and :func:`rotate` places it so; but the one cell that runs
so, twelve scanned layers under `fsdp=4`, lost 2.6 % with it, for a pass
worth 0.5 % on one chip, so the rule keeps the product there until a trace
says why: PERF.md, PR 53.)  Not a TPU: Pallas' interpret mode, which only a test asks for
(:func:`on_chip`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.ops.placement import place

LANES = 128
_F32 = jnp.float32
#: positions a chunk of the in-kernel loop: two packed bf16 sublane tiles
_CHUNK = 32


def on_chip() -> bool:
    """Whether the kernel is a program of this backend.  Off the chip the
    product is the pass; a test that wants the interpreter's run of the
    kernel replaces this function."""
    return jax.default_backend() == "tpu"


def blocks(positions: int, heads: Sequence[int], hd: int,
           itemsize: int) -> Optional[Tuple[int, int]]:
    """-> (the positions of a grid step, the groups the heads are walked in)
    for arrays of ``heads`` heads each over tables of ``positions`` rows: the
    most groups that divide every array's heads and leave a step eight heads
    of 128 lanes (one group where none does); then the most positions, up to
    512, that divide the row and whose step fits 3 MiB (its blocks in and
    out, each in two buffers, then take 12 of the 16 MiB of scoped VMEM).
    None where no block of whole chunks does.  The sweep read no difference
    between blocks of 64 to 512 positions, nor between one group and eight
    (``scripts/rope_pass_sweep.py``, PERF.md PR 53)."""
    shared = math.gcd(*heads)
    groups = max(g for g in range(1, shared + 1) if shared % g == 0
                 and (g == 1 or sum(heads) * hd // g >= 8 * LANES))
    step = sum(heads) // groups * hd * itemsize
    block = next((b for b in (512, 256, 128, 64, 32)
                  if positions % b == 0 and b * step <= 3 << 20), None)
    return block and (block, groups)


def path(shapes, interleave: bool, copies: int, mesh) -> str:
    """-> ``"kernel"`` or ``"product"``: which form a rotary call over
    arrays of these (B, S, H, hd) ``shapes`` takes under ``mesh`` (the
    module's docstring has the rule)."""
    B, S, _, hd = shapes[0]
    heads = [shape[2] for shape in shapes]
    # (float32's four bytes: a block that fits them fits every dtype)
    if (hd % LANES == 0 and not interleave and on_chip()
            and S % copies == 0 and blocks(S // copies, heads, hd, 4)
            and (mesh.empty or mesh.size == 1)):
        return "kernel"
    return "product"  # the shape, the pairing, the backend; or a mesh


def _kernel(cos_ref, sin_ref, *refs, hd: int, half: int, before: int,
            head_major_in: bool, head_major_out: bool, scales, backward: bool):
    """A grid step: ``refs`` the arrays' blocks, inputs then outputs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ins, outs = refs[:len(refs) // 2], refs[len(refs) // 2:]
    lane = lax.broadcasted_iota(jnp.int32, (_CHUNK, hd), 1)
    # the first lanes of the pairs look half a part up for their partner,
    # the second down; where the part is the whole head the two rolls are one
    looks_up = (lane >= before) & (lane < before + half)

    def chunk(c, carry):
        at = pl.ds(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK)
        cos, sin = cos_ref[at, :], sin_ref[at, :]
        for x_ref, out_ref, scale in zip(ins, outs, scales):
            heads = x_ref.shape[1] if head_major_in else x_ref.shape[2] // hd
            for h in range(heads):
                lanes = slice(h * hd, (h + 1) * hd)
                x = (x_ref[0, h, at, :] if head_major_in
                     else x_ref[0, at, lanes]).astype(_F32)
                if scale and backward:  # the scale's transpose comes first
                    x = x * scale
                partner = pltpu.roll(x, hd - half, 1)      # [i] = x[i + half]
                if 2 * half != hd:
                    partner = jnp.where(looks_up, partner,
                                        pltpu.roll(x, half, 1))
                y = x * cos + partner * sin
                if scale and not backward:
                    y = y * scale
                y = y.astype(out_ref.dtype)
                if head_major_out:
                    out_ref[0, h, at, :] = y
                else:
                    out_ref[0, at, lanes] = y
        return carry

    lax.fori_loop(0, cos_ref.shape[0] // _CHUNK, chunk, None)


def call(cos, sin, xs, *, half: int, before: int, head_major_in: bool,
         head_major_out: bool, block: int, groups: int, scales=None,
         backward: bool = False):
    """The ``pallas_call``.  cos, sin: (T, hd) float32; ``xs``: arrays of one
    dtype, (B, S, H x hd) each, or with ``head_major_in`` (B, H, S, hd), S a
    multiple of T (a row is S / T copies that restart their positions).
    -> as many arrays, (B, H, S, hd) with ``head_major_out`` else (B, S, H x
    hd).  ``block`` positions and a ``groups``-th of each array's heads a
    grid step (:func:`blocks` is the rule).  ``scales``: a factor an array
    or None, already a value of the arrays' dtype; it multiplies what the
    forward writes and what the ``backward`` reads, in float32."""
    # pallas is imported where a kernel is built, as the splash library and
    # the grouped products are: importing a model does not pay for it
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, hd = cos.shape
    B, S = (xs[0].shape[0], xs[0].shape[2 if head_major_in else 1])
    heads = [x.shape[1] if head_major_in else x.shape[2] // hd for x in xs]
    table_blocks = T // block

    def spec(H: int, head_major: bool):
        if head_major:
            return pl.BlockSpec((1, H // groups, block, hd),
                                lambda b, i, g: (b, g, i, 0))
        return pl.BlockSpec((1, block, H // groups * hd),
                            lambda b, i, g: (b, i, g))

    def shape(H: int, dtype):
        return jax.ShapeDtypeStruct(
            (B, H, S, hd) if head_major_out else (B, S, H * hd), dtype)

    table = pl.BlockSpec((block, hd), lambda b, i, g: (i % table_blocks, 0))
    return pl.pallas_call(
        functools.partial(_kernel, hd=hd, half=half, before=before,
                          head_major_in=head_major_in,
                          head_major_out=head_major_out,
                          scales=scales or (None,) * len(xs),
                          backward=backward),
        grid=(B, S // block, groups),
        in_specs=[table, table] + [spec(H, head_major_in) for H in heads],
        out_specs=[spec(H, head_major_out) for H in heads],
        out_shape=[shape(H, x.dtype) for H, x in zip(heads, xs)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=jax.default_backend() != "tpu",
        name="rope_backward" if backward else "rope_forward",
    )(cos, sin, *xs)


def rotate(cos, sin, xs, *, half: int, before: int, backward: bool,
           scales=None):
    """``xs``, (B, S, H, hd) arrays of one dtype, rotated by the tables
    ((S / copies, hd) float32: the caller's cos and signed sin), each in its
    own shape and dtype, by one Mosaic call placed where one may sit.  The
    forward hands each array over head-major under a transpose the splash
    call's own undoes; ``backward`` (the caller negated the sine) reads its
    cotangents so.  ``scales``: a factor an array or None; an array leaves
    the forward multiplied by its factor (the factor a value of x's dtype,
    as ``x * factor`` makes it; the product in float32 before the pass's one
    rounding, as XLA fuses ``rope(x) * factor``: it keeps the precision it
    has), and the backward multiplies its cotangent so first."""
    if scales is not None:
        scales = tuple(s and float(np.asarray(s, xs[0].dtype)) for s in scales)

    def local(cos, sin, *xs):
        B, S = xs[0].shape[:2]
        T, hd = cos.shape
        # a chip's own heads, where the mesh cuts them
        block, groups = blocks(T, [x.shape[2] for x in xs], hd,
                               xs[0].dtype.itemsize)
        if backward:
            laid = [x.transpose(0, 2, 1, 3) for x in xs]
        else:
            laid = [x.reshape(B, S, -1) for x in xs]
        out = call(cos, sin, laid, half=half, before=before,
                   head_major_in=backward, head_major_out=not backward,
                   block=block, groups=groups, scales=scales,
                   backward=backward)
        if backward:
            return tuple(o.reshape(x.shape) for o, x in zip(out, xs))
        return tuple(o.transpose(0, 2, 1, 3) for o in out)

    return place(local, (cos, sin, *xs), ("", "") + ("rh",) * len(xs),
                 ("rh",) * len(xs))
