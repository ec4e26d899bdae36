"""``ops/placement.py``: where a Mosaic call may sit on a mesh, on the CPU's
virtual devices.  The mesh's half of ``ops.ssd.path`` and ``ops.kda.path``
(their tests keep the shapes' half and hold every case against the whole
rule); what the splash call and the expert layer, which have no other path,
get under axes the rule does not name."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import placement
from ray_tpu.parallel import MeshSpec, make_mesh
from tests import families


#: ``MeshSpec`` names all six axes, those of size one too
NAMED = (("data", "fsdp"), "tensor")
#: the mesh's axes; rows and heads of the call; what ``rows_and_heads`` says
MESHES = {
    "no-mesh": ({}, (2, 8), ((), None)),
    "one-device": ({"data": 1}, (2, 8), ((), None)),
    "data4": ({"data": 4}, (4, 8), NAMED),
    "data2.fsdp2": ({"data": 2, "fsdp": 2}, (4, 8), NAMED),
    "data2.tensor2": ({"data": 2, "tensor": 2}, (2, 8), NAMED),
    "tensor4": ({"tensor": 4}, (1, 8), NAMED),
    "rows-it-does-not-divide": ({"data": 4}, (2, 8), None),
    "heads-it-does-not-divide": ({"data": 2, "tensor": 2}, (2, 3), None),
    "seq4": ({"seq": 4}, (4, 8), None),
    "data2.expert2": ({"data": 2, "expert": 2}, (4, 8), None),
}


@pytest.mark.parametrize("name", MESHES)
def test_the_axes_that_cut_rows_and_heads(name):
    """`data` and `fsdp` cut the rows and `tensor` the heads where those are
    all the mesh's axes larger than one and divide them; any other axis, or
    a remainder, is refused (a caller with another path then takes it)."""
    axes, (rows, heads), want = MESHES[name]
    mesh = families.mesh(**axes).abstract_mesh if axes \
        else jax.sharding.get_abstract_mesh()
    assert placement.rows_and_heads(mesh, rows, heads) == want
    if want is not None:
        assert placement.axes(mesh) == want


def test_axes_a_mesh_does_not_have_are_not_named():
    mesh = jax.make_mesh((2, 2), ("fsdp", "seq")).abstract_mesh
    assert placement.axes(mesh) == (("fsdp",), None)
    assert placement.rows_and_heads(mesh, 4, 8) is None  # `seq`
    alone = jax.make_mesh((4,), ("data",)).abstract_mesh
    assert placement.rows_and_heads(alone, 4, 3) == (("data",), None)


def _cube(x, scale, bias):
    """A stand-in for a kernel: elementwise over (rows, positions, heads,
    width) with a vector a head and one every chip reads whole; -> (the
    array, how many rows the call saw)."""
    return x * scale[:, None] + bias, jnp.full((1,), x.shape[0])


@pytest.mark.parametrize("name", ["no-mesh", "one-device", "data4",
                                  "data2.tensor2", "seq4"])
def test_place_wraps_the_call_where_the_mesh_has_devices(name):
    """One device: the call itself, no ``shard_map`` in the program.  A mesh:
    a ``shard_map`` over its rows' and heads' axes, every device working on
    its own block (the local shapes say so) and the results put together by
    the out specs; under `seq`, which the specs do not name, every device
    sees the whole arrays."""
    axes = MESHES[name][0]
    x = jnp.arange(4 * 6 * 8 * 2, dtype=jnp.float32).reshape(4, 6, 8, 2)
    scale, bias = jnp.arange(8.0), jnp.ones((2,))
    seen = []

    def local(x, scale, bias):
        seen.append((x.shape, scale.shape, bias.shape))
        return _cube(x, scale, bias)

    def placed(x, scale, bias):
        return placement.place(local, (x, scale, bias), ("rh", "h", ""),
                               ("rh", "r"))

    if not axes:
        jaxpr = jax.make_jaxpr(placed)(x, scale, bias)
        got = placed(x, scale, bias)
    else:
        with jax.set_mesh(families.mesh(**axes)):
            jaxpr = jax.make_jaxpr(placed)(x, scale, bias)
            got = jax.jit(placed)(x, scale, bias)
    rows = math.prod(axes.get(a, 1) for a in ("data", "fsdp"))
    heads = axes.get("tensor", 1)
    assert seen[0] == ((4 // rows, 6, 8 // heads, 2), (8 // heads,), (2,))
    sharded = math.prod(axes.values()) > 1 if axes else False
    assert ("shard_map" in str(jaxpr)) == sharded
    np.testing.assert_array_equal(got[0], _cube(x, scale, bias)[0])
    # an entry for each shard of the rows
    np.testing.assert_array_equal(got[1], [4 // rows] * rows)
    if sharded:
        assert "check_vma=False" in str(jaxpr)
