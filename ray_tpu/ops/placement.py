"""Where a Mosaic call may sit on a mesh.

The SPMD partitioner cannot cut a Pallas kernel's custom call, and jax
refuses to lower one it would have to ("Mosaic kernels cannot be
automatically partitioned").  So a kernel runs as it is with no ambient mesh
(``jax.set_mesh``; ``jit_train_step(mesh=)`` installs it) or on a mesh of one
device, and under a larger mesh inside a ``jax.shard_map`` in which every
chip works on rows and heads of its own: the rows (the batch) cut over the
mesh's `data` and `fsdp` axes, the heads (or groups, or nothing) over
`tensor`.  The splash call, the two scans' kernels and the expert layer's
grouped matmuls are placed here, by the one rule.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec


def axes(mesh):
    """-> (the axes of ``mesh`` that cut a placed call's rows, those of
    `data` and `fsdp` it has; the one that cuts its heads, `tensor`, or
    None); () and None for no mesh and for one device."""
    if mesh.empty or mesh.size == 1:
        return (), None
    return (tuple(a for a in ("data", "fsdp") if a in mesh.axis_names),
            "tensor" if "tensor" in mesh.axis_names else None)


def rows_and_heads(mesh, rows: int, heads: int):
    """:func:`axes` where those are all of ``mesh``'s axes and divide
    ``rows`` and ``heads`` (or groups), so that every device has rows and
    heads of its own to work on; else None: positions, or nothing the rule
    knows, are cut.  A caller with another path for such a mesh asks here
    first (the two scans: their XLA form)."""
    over, tensor = axes(mesh)
    cut = math.prod(mesh.shape[a] for a in over)
    heads_cut = mesh.shape[tensor] if tensor else 1
    if not (mesh.empty or cut * heads_cut == mesh.size) or rows % cut \
            or heads % heads_cut:
        return None
    return over, tensor


def place(local, args, dims, out_dims):
    """``local(*args)`` where a Mosaic call may sit.  No mesh or one device:
    the call itself.  Else a ``shard_map`` of it over :func:`axes`, in which
    ``dims`` says of each argument which axis is its rows and which its
    heads: ``"rh"`` for (rows, positions, heads, ...), ``"r"`` for rows in
    front and the rest whole, ``"h"`` for heads in front, ``""`` for an
    array every chip reads whole; ``out_dims`` the same for the results, a
    tree of such strings.  Axes the specs do not name see whole arrays and
    compute alike (the splash call and the expert layer have no other path,
    and run so under `seq` or `expert`)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return local(*args)
    over, tensor = axes(mesh)
    specs = {"rh": PartitionSpec(over or None, None, tensor),
             "r": PartitionSpec(over or None), "h": PartitionSpec(tensor),
             "": PartitionSpec()}
    # check_vma off: a pallas_call declares no vma on its output avals, which
    # the checker rejects.
    return jax.shard_map(
        local, in_specs=tuple(specs[d] for d in dims),
        out_specs=jax.tree.map(specs.get, out_dims),
        check_vma=False)(*args)
