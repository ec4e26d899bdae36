"""Elastic restore: place restored host leaves onto a *different* mesh.

Shards on disk record the writer's world size, but assemble_tree already
reconciles that into full host arrays — so restoring into a new topology
is purely a placement problem: device_put every leaf with a sharding
derived from the new mesh.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import numpy as np


def default_pspec(leaf: np.ndarray, mesh) -> "Any":
    """Shard axis 0 over the mesh's first axis when it divides evenly;
    replicate otherwise — the mirror of layout.partition_for."""
    from jax.sharding import PartitionSpec

    axis_names = list(mesh.axis_names)
    if not axis_names:
        return PartitionSpec()
    first = axis_names[0]
    size = int(np.prod([mesh.shape[a] for a in (first,)]))
    if leaf.ndim >= 1 and size > 1 and leaf.shape[0] % size == 0:
        return PartitionSpec(first)
    return PartitionSpec()


def reshard_tree(host_tree: Any, mesh, pspec: Optional[Any] = None,
                 pspec_fn: Optional[Callable] = None) -> Any:
    """device_put every leaf of a host pytree onto ``mesh``.

    ``pspec`` — one PartitionSpec for every leaf (leaves it cannot apply
    to fall back to replication); ``pspec_fn(leaf, mesh) -> PartitionSpec``
    — per-leaf control; neither — ``default_pspec``.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    def place(leaf):
        a = np.asarray(leaf)
        if pspec_fn is not None:
            spec = pspec_fn(a, mesh)
        elif pspec is not None:
            spec = pspec
        else:
            spec = default_pspec(a, mesh)
        try:
            return jax.device_put(a, NamedSharding(mesh, spec))
        except ValueError:
            # Spec does not divide this leaf (e.g. a scalar under a fixed
            # user pspec): replicate rather than fail the restore.
            return jax.device_put(a, NamedSharding(mesh, PartitionSpec()))

    return jax.tree.map(place, host_tree)
