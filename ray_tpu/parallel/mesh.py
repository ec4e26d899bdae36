"""Device mesh construction and named parallelism axes.

The reference has no native TP/PP/SP (SURVEY §2.3: delegated to DeepSpeed/HF
over Ray-provided process groups).  Here parallelism is first-class: a
``MeshSpec`` names the six standard axes and maps them onto the physical
device grid; shardings are expressed as PartitionSpecs over these names and
XLA inserts the collectives (psum for dp/fsdp grad sync, all-gather for fsdp
params, all-to-all/ppermute for sp) — the scaling-book recipe.  The one
collective written by hand is the reduction of the Llama layers' weight
gradients over `fsdp` (``ops/grad_ring.py``: a ring of ``ppermute``s between
chunk products, where XLA's reduce-scatter would run alone).

Axes (outermost → innermost = slowest → fastest links):
  pipe   — pipeline parallel (GPipe microbatch schedule, parallel/pipeline.py;
           stage handoffs are point-to-point ppermutes, so this axis tolerates
           the slowest links — put it across DCN on multi-slice)
  data   — pure data parallel (gradient psum)
  fsdp   — data parallel with parameter/optimizer sharding (ZeRO-3 equiv:
           XLA all-gathers params per layer and reduce-scatters grads, but
           the Llama layers' weight gradients, which ops/grad_ring.py sums)
  expert — where MoE expert weights are stored (sharded on their expert
           axis; models/moe.py gathers them to compute, tokens stay put:
           no all_to_all dispatch is built)
  tensor — megatron-style tensor parallel (activations psum)
  seq    — sequence/context parallel (ring attention / all-to-all)
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

AXIS_NAMES = ("pipe", "data", "fsdp", "expert", "tensor", "seq")


@dataclass(frozen=True)
class MeshSpec:
    data: int = 1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1

    @property
    def size(self) -> int:
        return (self.pipe * self.data * self.fsdp * self.expert
                * self.tensor * self.seq)

    def axis_sizes(self) -> Dict[str, int]:
        return {"pipe": self.pipe, "data": self.data, "fsdp": self.fsdp,
                "expert": self.expert, "tensor": self.tensor, "seq": self.seq}

    @staticmethod
    def auto(n_devices: int, tensor: int = 1, seq: int = 1,
             fsdp: Optional[int] = None, pipe: int = 1,
             expert: int = 1) -> "MeshSpec":
        """Fill the data axis with whatever the other axes don't consume."""
        inner = tensor * seq * (fsdp or 1) * pipe * expert
        if n_devices % inner != 0:
            raise ValueError(
                f"{n_devices} devices not divisible by "
                f"pipe*expert*tensor*seq*fsdp={inner}")
        return MeshSpec(data=n_devices // inner, fsdp=fsdp or 1,
                        tensor=tensor, seq=seq, pipe=pipe, expert=expert)


def make_mesh(spec: MeshSpec, devices: Optional[Sequence] = None):
    """Build a jax Mesh with the canonical axis order
    (pipe, data, fsdp, expert, tensor, seq).

    Device order matters on real hardware: JAX returns devices in
    topology-aware order, so the innermost axes (tensor, seq) land on
    ICI-adjacent chips, keeping the chattiest collectives on the shortest
    links — the analogue of the reference packing PG bundles onto one node.
    """
    import jax

    devices = list(devices if devices is not None else jax.devices())
    if spec.size > len(devices):
        raise ValueError(f"MeshSpec needs {spec.size} devices, have {len(devices)}")
    grid = np.array(devices[: spec.size]).reshape(
        spec.pipe, spec.data, spec.fsdp, spec.expert, spec.tensor, spec.seq)
    return jax.sharding.Mesh(grid, AXIS_NAMES)


def partition(*axes) -> "jax.sharding.PartitionSpec":  # noqa: F821
    import jax

    return jax.sharding.PartitionSpec(*axes)


def named_sharding(mesh, *axes):
    import jax

    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*axes))


def batch_sharding(mesh, rules: Optional[Dict] = None):
    """Sharding for a (batch, seq) token array — the one true place that
    encodes batch->(data,fsdp), seq->seq so call sites can't drift."""
    import jax

    return jax.sharding.NamedSharding(mesh, logical_to_spec(("batch", "seqlen"), rules))


# Logical axis rules: model code annotates params with logical axis names and
# these rules map them to mesh axes (the flax/t5x "logical axes" idea, kept
# dependency-free).
DEFAULT_RULES: Dict[str, Optional[Tuple[str, ...]]] = {
    "vocab": ("tensor",),
    "embed": ("fsdp",),
    "heads": ("tensor",),
    "kv": None,
    "mlp": ("tensor",),
    "batch": ("data", "fsdp"),
    "seqlen": ("seq",),
    "norm": None,
    # Leading stacked-layer axis of a pipelined block stack: sharding it over
    # `pipe` gives each stage its slice of layers (parallel/pipeline.py).
    "layers": ("pipe",),
    # Leading expert axis of the expert weights (models/llama.py with
    # experts): where they are stored; models/moe.py gathers them to compute.
    "expert": ("expert",),
}


def logical_to_spec(logical_axes: Sequence[Optional[str]],
                    rules: Optional[Dict] = None):
    """('vocab','embed') -> PartitionSpec(('tensor',), ('fsdp',))."""
    import jax

    rules = rules or DEFAULT_RULES
    spec = []
    for name in logical_axes:
        if name is None:
            spec.append(None)
            continue
        mapped = rules.get(name)
        if mapped is None:
            spec.append(None)
        elif len(mapped) == 1:
            spec.append(mapped[0])
        else:
            spec.append(tuple(mapped))
    return jax.sharding.PartitionSpec(*spec)


def shard_pytree(tree, logical_tree, mesh, rules: Optional[Dict] = None):
    """device_put a parameter pytree according to its logical axis pytree."""
    import jax

    def place(x, logical):
        return jax.device_put(x, jax.sharding.NamedSharding(mesh, logical_to_spec(logical, rules)))

    return jax.tree.map(place, tree, logical_tree)


def pytree_sharding(logical_tree, mesh, rules: Optional[Dict] = None):
    """NamedSharding pytree (for jit in_shardings/out_shardings)."""
    import jax

    def to_sharding(logical):
        return jax.sharding.NamedSharding(mesh, logical_to_spec(logical, rules))

    return jax.tree.map(to_sharding, logical_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(a, (str, type(None))) for a in x))
