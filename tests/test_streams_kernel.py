"""``ops/streams_kernel.py``: the hyper-connections' four Mosaic passes under
the interpreter against the expressions of ``models/streams.py`` (``maps``,
``read``, ``write`` and ``jax.vjp`` of them), values and every cotangent; the
rule that picks the form; what a sub-layer notes of it."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from ray_tpu.models import streams
from ray_tpu.ops import remat, streams_kernel
from ray_tpu.util import first_call

F32, BF16 = jnp.float32, jnp.bfloat16
N = 4


def _config(C, clamp=(-10.0, 10.0)):
    return types.SimpleNamespace(
        streams=N, d_model=C, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_clamp=clamp, rms_eps=1e-6)


def _drawn(key, shape, dtype=F32, scale=1.0):
    return (jax.random.normal(jax.random.key(key), shape, F32)
            * scale).astype(dtype)


def _off(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def _operands(B, S, C, dtype, config, moved=True):
    """The streams, ``y`` and a sub-layer's row of ``hc``, the maps moved off
    their start as ``tests/test_xing4.py`` moves them."""
    X = tuple(_drawn(1 + j, (B, S, C), dtype) for j in range(N))
    hc = jax.tree.map(lambda a: a[0],
                      streams.init_params(config, jax.random.key(9), 1))
    if moved:
        hc = {"phi": hc["phi"] * 5, "alpha": hc["alpha"] * 100,
              "base": hc["base"] * 0.1}
    return X, _drawn(7, (B, S, C), dtype), hc


def _xla(config):
    def f(X, y, hc):
        H, _ = streams.maps(X, hc, config)
        return H, streams.read(X, H), streams.write(X, y, H)
    return f


def _kernel(config):
    def f(X, y, hc):
        H, u, X = streams_kernel.maps_read(X, hc, config)
        return H, u, streams_kernel.write(X, y, H)
    return f


#: name -> (B, S, C, dtype, the row blocks, the clamp, the maps moved)
CASES = {
    # two grid steps: phi's, alpha's and base's cotangents add up across them
    "float32_two_blocks": (2, 128, 256, F32, (128,), (-10.0, 10.0), True),
    "float32_one_block_of_256": (1, 256, 128, F32, (256, 128),
                                 (-10.0, 10.0), True),
    "float32_three_blocks": (3, 128, 128, F32, (256, 128), (-10.0, 10.0),
                             True),
    "float32_maps_at_their_start": (1, 128, 256, F32, (128,), (-10.0, 10.0),
                                    False),
    # the off-diagonal logits start at -8 x 0.1 and alpha x 100 spreads them
    # past +-1: most entries sit on the clamp and pass no gradient
    "float32_clamp_binds": (1, 128, 256, F32, (128,), (-1.0, 1.0), True),
    "bfloat16": (2, 128, 256, BF16, (128,), (-10.0, 10.0), True),
    # Xing4.0's row: four streams of 3584 lanes, 28 lane tiles
    "published_row_two_blocks": (1, 256, 3584, F32, (128,), (-10.0, 10.0),
                                 True),
}


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(streams_kernel, "on_chip", lambda: True)


def _both(name, monkeypatch):
    B, S, C, dtype, blocks, clamp, moved = CASES[name]
    monkeypatch.setattr(streams_kernel, "BLOCKS", blocks)
    config = _config(C, clamp)
    operands = _operands(B, S, C, dtype, config, moved)
    want, pull_want = jax.vjp(_xla(config), *operands)
    got, pull_got = jax.vjp(_kernel(config), *operands)
    return dtype, operands, (want, pull_want), (got, pull_got)


@pytest.mark.parametrize("name", CASES)
def test_the_passes_are_xlas_values(name, interpreted, monkeypatch):
    """``H``, ``u`` and the new streams: float32 to 1e-5, bf16 within one
    rounding of the output."""
    dtype, _, (want, _), (got, _) = _both(name, monkeypatch)
    assert [a.dtype for a in jax.tree.leaves(got)] \
        == [a.dtype for a in jax.tree.leaves(want)]
    assert [a.shape for a in jax.tree.leaves(got)] \
        == [a.shape for a in jax.tree.leaves(want)]
    tol = 1e-5 if dtype == F32 else 2.0 ** -7
    assert max(_off(a, b) for a, b in zip(jax.tree.leaves(got),
                                          jax.tree.leaves(want))) < tol
    if name == "float32_clamp_binds":
        res = np.asarray(got[0][..., 2 * N:])
        assert res.min() > 0.0  # every entry exp(clip()) > 0, turned


@pytest.mark.parametrize("name", CASES)
def test_the_passes_are_xlas_cotangents(name, interpreted, monkeypatch):
    """Of ``X``, ``y``, ``phi``, ``alpha`` and ``base``, pulled back along
    drawn cotangents of ``H``, ``u`` and the new streams at once."""
    dtype, _, (want, pull_want), (_, pull_got) = _both(name, monkeypatch)
    ct = jax.tree.map(lambda a: _drawn(11, a.shape, a.dtype), want)
    dwant, dgot = pull_want(ct), pull_got(ct)
    assert jax.tree.structure(dgot) == jax.tree.structure(dwant)
    assert [a.dtype for a in jax.tree.leaves(dgot)] \
        == [a.dtype for a in jax.tree.leaves(dwant)]
    tol = 1e-5 if dtype == F32 else 2e-2
    off = {jax.tree_util.keystr(path): _off(a, b)
           for (path, a), b in zip(
               jax.tree_util.tree_leaves_with_path(dgot),
               jax.tree.leaves(dwant))}
    assert max(off.values()) < tol, off


def test_the_clamp_passes_no_gradient_where_it_binds(interpreted,
                                                     monkeypatch):
    """With every stream-map logit past the clamp the maps' parameters meet
    the turns through nothing: ``H_res`` is the turned constant and its
    cotangent reaches ``phi`` through the gates alone."""
    monkeypatch.setattr(streams_kernel, "BLOCKS", (128,))
    config = _config(128, (-1e-3, 1e-3))
    X, y, hc = _operands(1, 128, 128, F32, config)
    hc["base"] = hc["base"].at[2 * N:].set(5.0)

    def res(hc, f):
        return jnp.sum(f(X, y, hc)[0][..., 2 * N:]
                       * _drawn(3, (1, 128, N * N)))

    got = jax.grad(res)(hc, _kernel(config))
    want = jax.grad(res)(hc, _xla(config))
    for leaf in ("phi", "alpha", "base"):
        assert not np.asarray(got[leaf]).any(), leaf
        assert not np.asarray(want[leaf]).any(), leaf


def _branch(u, blk):
    return jnp.tanh(u) * blk, None


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_a_sub_layer_runs_the_kernels_where_the_rule_says(dtype,
                                                          monkeypatch):
    """``streams.layer`` under a checkpoint, as a step runs it: off the chip
    XLA's expressions; with ``on_chip`` replaced the kernels, with the same
    streams, gradients and ``mhc_sinkhorn_err``, and the record names the
    path and counts the sub-layers."""
    monkeypatch.setattr(streams_kernel, "BLOCKS", (128,))
    config = _config(256)
    X, _, hc = _operands(2, 128, 256, dtype, config)
    blk = _drawn(5, (256,), dtype)

    def run(X, blk, hc):
        first, second = (jax.checkpoint(streams.layer(config, _branch))
                         for _ in range(2))
        new, counted = first(X, blk, hc)
        new, again = second(new, blk, hc)
        return sum(jnp.sum(x.astype(F32) ** 2) for x in new), \
            (counted, again)

    def traced():
        with first_call.noting() as notes:
            (loss, counted), grads = jax.value_and_grad(
                lambda *a: run(*a), argnums=(0, 1, 2), has_aux=True)(
                    X, blk, hc)
        return notes, loss, counted, grads

    notes, want, counted_want, dwant = traced()
    assert notes == {"streams_kernel": "xla", "mhc_calls": 2}
    monkeypatch.setattr(streams_kernel, "on_chip", lambda: True)
    notes, got, counted_got, dgot = traced()
    assert notes == {"streams_kernel": "kernel", "mhc_calls": 2}
    tol = 1e-5 if dtype == F32 else 3e-2
    assert abs(float(got) - float(want)) < tol * abs(float(want))
    assert max(_off(a, b) for a, b in zip(jax.tree.leaves(dgot),
                                          jax.tree.leaves(dwant))) < tol
    for a, b in zip(jax.tree.leaves(counted_got),
                    jax.tree.leaves(counted_want)):
        assert abs(float(a) - float(b)) < 1e-5


def test_a_sub_layers_maps_keep_their_name(interpreted, monkeypatch):
    """``H`` leaves the kernels' pass under ``remat.MAPS`` too."""
    monkeypatch.setattr(streams_kernel, "BLOCKS", (128,))
    config = _config(128)
    X, _, hc = _operands(1, 128, 128, F32, config)
    jaxpr = jax.make_jaxpr(streams.layer(config, _branch))(
        X, _drawn(5, (128,)), hc)
    named = [eqn.params["name"] for eqn in jaxpr.jaxpr.eqns
             if eqn.primitive.name == "name"]
    assert named == [remat.MAPS]


_ONE = AbstractMesh((1,), ("data",))
_FOUR = AbstractMesh((4,), ("fsdp",))
_NONE = AbstractMesh((), ())
#: name -> (tokens, C, n, the mesh, what the rule answers on the chip)
RULE = {
    "the_cell": (8192, 3584, 4, _NONE, "kernel"),
    "a_mesh_of_one_device": (8192, 3584, 4, _ONE, "kernel"),
    "a_mesh_of_four": (8192, 3584, 4, _FOUR, "xla"),
    "lanes_of_no_whole_tile": (8192, 3600, 4, _NONE, "xla"),
    "tokens_the_block_does_not_divide": (8192 + 64, 3584, 4, _NONE, "xla"),
    "two_streams": (8192, 3584, 2, _NONE, "xla"),
    "one_block_of_128": (128, 128, 4, _NONE, "kernel"),
}


@pytest.mark.parametrize("name", RULE)
def test_the_rule(name, monkeypatch):
    """Off the chip XLA's expressions whatever the shapes; on it the
    kernels where a block divides the tokens, the lanes are whole tiles,
    the streams are four and no mesh shards them."""
    tokens, C, n, mesh, want = RULE[name]
    assert streams_kernel.path(tokens, C, n, 2, mesh) == "xla"  # this CPU
    monkeypatch.setattr(streams_kernel, "on_chip", lambda: True)
    assert streams_kernel.path(tokens, C, n, 2, mesh) == want
    rows = streams_kernel.block(tokens, C, n, 2)
    if want == "kernel":
        assert tokens % rows == 0 and rows % streams_kernel.LANES == 0
        assert streams_kernel._vmem(rows, C, n, 2) \
            <= streams_kernel.VMEM_MOST


def test_the_sizes_the_remat_rule_sees_are_the_paths(monkeypatch):
    """``streams.layer_bytes``: XLA's working set and what the maps kept
    spare off the chip; the kernels' (the write's backward: two sets of
    streams and ``y``'s cotangent) and nothing spared where they run."""
    config = _config(3584)
    working, kept, rungs = streams.layer_bytes(config, 8192, 2)
    assert (working, kept) == (3 * N * 8192 * 3584 * 2, 0)
    assert rungs[remat.MAPS][1] > 0
    monkeypatch.setattr(streams_kernel, "on_chip", lambda: True)
    working, kept, rungs = streams.layer_bytes(config, 8192, 2)
    assert (working, kept) == ((2 * N + 1) * 8192 * 3584 * 2, 0)
    assert rungs[remat.MAPS] == (8192 * 24 * 4, 0.0)
