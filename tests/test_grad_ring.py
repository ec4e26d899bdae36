"""``ops/grad_ring.py``: under an `fsdp` mesh axis the weight gradient of
``x @ w`` is a ring of chunk products whose partial sums move by ``ppermute``.
The CPU's virtual devices say whether the sums are right and where the ring
engages; that the sends hide behind the products only a chip can say."""

import contextlib
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import grad_ring
from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh
from ray_tpu.parallel.mesh import pytree_sharding
from ray_tpu.parallel.train_state import jit_train_step
from ray_tpu.util import device_telemetry, first_call

LAYOUTS = {"fsdp4": MeshSpec(fsdp=4), "data2.fsdp2": MeshSpec(data=2, fsdp=2),
           "fsdp2.tensor2": MeshSpec(fsdp=2, tensor=2),
           "data2.fsdp4": MeshSpec(data=2, fsdp=4)}
#: every dense projection of ``llama._block``
RING_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _rings(seen):
    """The ring's two facts among whatever else a trace noted."""
    return {name: seen[name] for name in grad_ring.NO_RINGS}


def _float32_tiny():
    """``LlamaConfig.tiny()`` in float32 on the einsum attention, so that
    the partitioner's gradients and the ring's differ by round-off only."""
    return dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32,
                               logits_dtype=jnp.float32, attn_impl="xla")


def _batch(config, rows=8):
    tokens = jax.random.randint(jax.random.key(1), (rows, config.seq_len), 0,
                                config.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


@pytest.fixture(scope="module")
def partitioner():
    """(config, params, batch, the gradients with every product left to the
    partitioner on one device)."""
    config = _float32_tiny()
    params = llama.init_params(config, jax.random.key(0))
    batch = _batch(config)
    grads = jax.jit(jax.grad(partial(llama.loss_fn, config=config)))(
        params, *batch)
    return config, params, batch, grads


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_tiny_llama_gradients_equal_the_partitioners(partitioner, layout):
    config, params, batch, want = partitioner
    mesh = make_mesh(LAYOUTS[layout])
    params = jax.device_put(
        params, pytree_sharding(llama.logical_axes(config), mesh))
    batch = [jax.device_put(a, batch_sharding(mesh)) for a in batch]
    with jax.set_mesh(mesh), first_call.noting(**grad_ring.NO_RINGS) as seen:
        got = jax.jit(jax.grad(partial(llama.loss_fn, config=config)))(
            params, *batch)
    assert _rings(seen) == {"grad_ring_products": len(RING_WEIGHTS),
                            "grad_ring_axis": mesh.shape["fsdp"]}
    for name in RING_WEIGHTS:
        g, w = got["blocks"][name], want["blocks"][name]
        assert float(jnp.max(jnp.abs(g - w))) < 1e-5 * float(
            jnp.max(jnp.abs(w))), name
        # the shard a chip ends with is the one it owns
        assert "fsdp" in jax.tree.leaves(tuple(g.sharding.spec)), name
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(g - w))) < 1e-5 * float(
            jnp.max(jnp.abs(w)))


@pytest.mark.parametrize("embed", [0, 1], ids=["embed_first", "embed_last"])
@pytest.mark.parametrize("layout", ["fsdp4", "data2.fsdp2", "fsdp2.tensor2"])
def test_one_product_both_cuts(layout, embed):
    """dx and dW of one ``dense`` against ``x @ w``, the weight cut over
    `fsdp` along its first axis (wq, w_gate, ...) and its last (wo,
    w_down)."""
    mesh = make_mesh(LAYOUTS[layout])
    x = jax.random.normal(jax.random.key(0), (8, 16, 64), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (64, 96), jnp.float32)
    cut = ("fsdp", "tensor") if embed == 0 else ("tensor", "fsdp")
    cut = [a if a in mesh.axis_names else None for a in cut]

    def loss(product, x, w):
        return jnp.sum(jnp.sin(product(x, w)))

    want = jax.grad(partial(loss, jnp.matmul), argnums=(0, 1))(x, w)
    with jax.set_mesh(mesh):
        args = (jax.device_put(x, batch_sharding(mesh)),
                jax.device_put(w, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(*cut))))
        ring = jax.jit(jax.grad(
            partial(loss, partial(grad_ring.dense, embed=embed)),
            argnums=(0, 1)))
        assert "sdy.manual_computation" in ring.lower(*args).as_text()
        got = ring(*args)
    for g, w_ in zip(got, want):
        assert float(jnp.max(jnp.abs(g - w_))) < 1e-5 * float(
            jnp.max(jnp.abs(w_)))
    assert got[1].sharding.spec[embed] == "fsdp"


@pytest.mark.parametrize("embed", [0, 1], ids=["embed_first", "embed_last"])
def test_an_axis_that_does_not_divide_the_weight_is_refused(embed):
    mesh = make_mesh(MeshSpec(fsdp=4))
    x = jnp.ones((8, 16, 66 if embed == 0 else 64))
    w = jnp.ones((x.shape[-1], 64 if embed == 0 else 66))
    with jax.set_mesh(mesh), pytest.raises(ValueError, match="must divide"):
        jax.jit(jax.grad(lambda x, w: jnp.sum(
            grad_ring.dense(x, w, embed)), argnums=1))(x, w)


def test_a_batch_the_mesh_does_not_cut_is_left_to_the_partitioner():
    """Three rows over `fsdp=4`: the partitioner cannot cut them either, and
    the product is the plain one."""
    mesh = make_mesh(MeshSpec(fsdp=4))
    x, w = jnp.ones((3, 16, 64)), jnp.ones((64, 32))
    with jax.set_mesh(mesh), first_call.noting(**grad_ring.NO_RINGS) as seen:
        text = jax.jit(jax.grad(lambda x, w: jnp.sum(
            grad_ring.dense(x, w, 0)), argnums=1)).lower(x, w).as_text()
    assert "sdy.manual_computation" not in text and seen == grad_ring.NO_RINGS


def _hops(coords, order):
    """Manhattan lengths of the ring's hops, the closing one last."""
    points = [coords[i] for i in order]
    return [sum(abs(a - b) for a, b in zip(p, q))
            for p, q in zip(points, points[1:] + points[:1])]


@pytest.mark.parametrize("box", [(2, 2, 1), (4, 2, 1), (2, 4, 1), (2, 2, 2),
                                 (4, 4, 1)], ids=str)
def test_the_snake_goes_from_neighbour_to_neighbour(box):
    """Device coordinates as a TPU host lists them (x fastest).  Every hop
    but the closing one is one link long; the ring closes over one link
    where a side of the box is two chips wide (a v5e host's 2x2: mesh order
    0 1 2 3 crosses it diagonally twice, the snake is 0 1 3 2)."""
    coords = [(x, y, z) for z in range(box[2]) for y in range(box[1])
              for x in range(box[0])]
    order = grad_ring.snake(coords)
    assert sorted(order) == list(range(len(coords)))
    hops = _hops(coords, order)
    assert set(hops[:-1]) == {1}
    if box == (2, 2, 1):
        assert order == [0, 1, 3, 2] and hops[-1] == 1


def test_the_ring_follows_mesh_order_where_devices_have_no_coordinates():
    """The CPU's devices say nothing of where they sit."""
    assert grad_ring.ring_order(4) == [0, 1, 2, 3]  # no mesh at all
    with jax.set_mesh(make_mesh(MeshSpec(data=2, fsdp=4))):
        assert grad_ring.ring_order(4) == [0, 1, 2, 3]


def test_the_ring_follows_the_devices_coordinates(monkeypatch):
    """A mesh whose `fsdp` row sits on a 2x2 as a v5e host's chips do."""
    class Chip:
        def __init__(self, i):
            self.id, self.coords = i, (i % 2, i // 2, 0)

    class Square:
        empty, axis_names = False, ("data", "fsdp")
        devices = np.array([[Chip(i) for i in range(4)]], dtype=object)

    from jax._src import mesh as mesh_lib
    monkeypatch.setattr(mesh_lib, "get_concrete_mesh", lambda: Square)
    assert grad_ring.ring_order(4) == [0, 1, 3, 2]


def test_an_odd_chunk_travels_whole_and_one_way():
    """Six rows a chip's chunk over `fsdp=4` is three rows a half: fine;
    five is not, and the sums are still right."""
    mesh = make_mesh(MeshSpec(fsdp=4))
    x = jax.random.normal(jax.random.key(0), (8, 4, 20), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (20, 12), jnp.float32)
    want = jax.grad(lambda x, w: jnp.sum(jnp.sin(x @ w)), argnums=1)(x, w)
    with jax.set_mesh(mesh):
        ring = jax.jit(jax.grad(lambda x, w: jnp.sum(jnp.sin(
            grad_ring.dense(x, w, 0))), argnums=1))
        args = (jax.device_put(x, batch_sharding(mesh)), w)
        assert ring.lower(*args).as_text().count("collective_permute") == 3
        got = ring(*args)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))


def _step_and_state(config):
    optimizer = llama.make_optimizer()
    params = llama.init_params(config, jax.random.key(0))
    return (llama.make_train_step(config, optimizer), params,
            optimizer.init(params))


@pytest.mark.parametrize("mesh_spec", [None, MeshSpec(fsdp=1)],
                         ids=["no_mesh", "fsdp1"])
def test_on_one_device_the_step_holds_no_shard_map(mesh_spec):
    config = llama.LlamaConfig.tiny()
    step_fn, params, opt_state = _step_and_state(config)
    mesh = mesh_spec and make_mesh(mesh_spec, jax.devices()[:1])
    context = jax.set_mesh(mesh) if mesh is not None else \
        contextlib.nullcontext()
    with context, first_call.noting(**grad_ring.NO_RINGS) as seen:
        jaxpr = jax.make_jaxpr(step_fn)(params, opt_state, *_batch(config))
    text = str(jaxpr)
    assert "shard_map" not in text and "ppermute" not in text
    assert "custom_vjp_call" not in text.replace("_rope", "")
    assert _rings(seen) == grad_ring.NO_RINGS


def test_the_step_lowers_for_the_tpu_under_fsdp4(monkeypatch):
    """The whole train step, ring and splash kernel together, through the
    TPU's lowering rules on four virtual devices (as
    ``test_bringup`` lowers the kernel alone): the ring is a manual
    computation over `fsdp` with a collective-permute inside."""
    config = llama.LlamaConfig(
        vocab_size=1024, n_layer=2, n_head=4, n_kv_head=2, d_model=512,
        d_ff=1024, seq_len=512)
    mesh = make_mesh(MeshSpec(fsdp=4), jax.devices()[:4])
    optimizer = llama.make_optimizer()
    shardings = pytree_sharding(llama.logical_axes(config), mesh)
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(partial(llama.init_params, config),
                       jax.random.key(0)), shardings)
    opt_state = jax.eval_shape(optimizer.init, params)
    batch = jax.ShapeDtypeStruct((4, config.seq_len), jnp.int32,
                                 sharding=batch_sharding(mesh))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        text = jax.jit(llama.make_train_step(config, optimizer)).trace(
            params, opt_state, batch, batch).lower(
                lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text  # the splash kernel was lowered too
    sends = text.count("stablehlo.collective_permute")
    # (N - 1) sends of each half of each of the seven gradients, in the one
    # scanned body; every send's sum tied to the next product's operands
    assert sends == len(RING_WEIGHTS) * 3 * 2, sends
    assert text.count("stablehlo.optimization_barrier") >= sends


def test_first_call_says_how_many_rings_were_traced():
    config = llama.LlamaConfig.tiny()
    device_telemetry.reset()
    for mesh, want in ((None, (0, 0)),
                       (make_mesh(MeshSpec(fsdp=4), jax.devices()[:4]),
                        (len(RING_WEIGHTS), 4))):
        step_fn, params, opt_state = _step_and_state(config)
        batch = _batch(config)
        if mesh is not None:
            params = jax.device_put(
                params, pytree_sharding(llama.logical_axes(config), mesh))
            batch = [jax.device_put(a, batch_sharding(mesh)) for a in batch]
        step = jit_train_step(step_fn, mesh=mesh)
        _, _, loss = step(params, opt_state, *batch)
        assert jnp.isfinite(loss)
        row = device_telemetry.first_calls("train_step")[-1]
        assert (row["grad_ring_products"], row["grad_ring_axis"]) == want
        assert "remat_kept" in row  # beside the layer's other decision
