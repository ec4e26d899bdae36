"""``lib/state.py``: the optimizer a multi-chip cell hands the program, on
four virtual CPU devices under ``MeshSpec(fsdp=4)``."""

import jax
import numpy as np
import pytest

from benchmarks.lib import correct, spec, state
from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh
from ray_tpu.parallel.mesh import pytree_sharding
from ray_tpu.parallel.train_state import create_sharded_state


def build(preset, mesh_axes, n_devices):
    config = spec.load_json(spec.BENCH_DIR, "configs", preset + ".json")
    family = spec.load_module("models", config["family"]).build(config, 128)
    mesh = make_mesh(MeshSpec(**mesh_axes), jax.local_devices()[:n_devices])
    return family, mesh, pytree_sharding(family.logical_axes, mesh)


def bytes_on(device, tree):
    return sum(s.data.nbytes for leaf in jax.tree.leaves(tree)
               for s in leaf.addressable_shards if s.device == device)


@pytest.mark.parametrize("preset", ["tiny-llama", "tiny-gpt2", "tiny-olmoe"])
def test_moments_are_born_where_their_parameters_lie(preset):
    family, mesh, expected = build(preset, {"fsdp": 4}, 4)
    inner = family.make_optimizer()
    optimizer = state.born_sharded(inner, expected)
    assert optimizer.update is inner.update
    params, opt_state = create_sharded_state(
        family.init_fn, family.logical_axes, mesh, jax.random.key(0),
        optimizer)
    rows = jax.device_put(np.zeros((4, 128), np.int32), batch_sharding(mesh))
    placed = correct.placement(params, opt_state, rows, expected, 1, 4)
    assert placed["ok"] and placed["optimizer_mirrors"] == 2, placed
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(opt_state))
    # a quarter of the moments, plus what the layout replicates (norms,
    # counts): far from the whole of them, which is what the program's own
    # init leaves on every chip
    for device in mesh.devices.flat:
        assert bytes_on(device, opt_state) < 0.3 * total
    plain = create_sharded_state(
        family.init_fn, family.logical_axes, mesh, jax.random.key(0),
        family.make_optimizer())[1]
    assert bytes_on(jax.local_devices()[0], plain) == total  # ROADMAP A1
    # the same state, leaf for leaf
    assert jax.tree.structure(plain) == jax.tree.structure(opt_state)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(opt_state)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))


def test_one_device_gets_the_optimizer_itself():
    family, _, expected = build("tiny-llama", {}, 1)
    optimizer = family.make_optimizer()
    assert state.born_sharded(optimizer, expected) is optimizer


def test_the_check_gets_its_gradients_cut_as_the_parameters():
    """Left alone the compiler hands the program's gradients back whole on
    every chip, which at 12 layers no chip holds (PERF.md, PR 30)."""
    family, mesh, expected = build("tiny-llama", {"fsdp": 4}, 4)
    params, _ = create_sharded_state(family.init_fn, family.logical_axes,
                                     mesh, jax.random.key(0))
    rows = jax.device_put(np.zeros((4, 128), np.int32), batch_sharding(mesh))
    with jax.set_mesh(mesh):
        _, grads = correct.value_and_grad(family.loss_fn, params)(
            params, rows, rows)
    for grad, want in zip(jax.tree.leaves(grads), jax.tree.leaves(expected)):
        assert grad.sharding.is_equivalent_to(want, grad.ndim)
