"""The delta-rule scan's Pallas kernels (``ops/kda_kernel.py``) in interpret
mode, at sizes that lie on the chip's tiles (heads of 128, chunks of 64, two
rows, two or three heads): against the float32 recurrence position by
position (``benchmarks/reference``, at the tolerances
``tests/test_solar_open2.py`` holds the XLA form to), against the XLA form on
the same inputs, and which of the two a call takes (``ops.kda.path``).  That
the cell's shape compiles for the v5e is in ``tests/test_attention_blocks.py``
beside the other kernels' compiles (one file loads the TPU's compiler).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import solar_open2 as reference
from ray_tpu.ops import kda as kda_module
from ray_tpu.ops import kda_kernel
from ray_tpu.ops.kda import kda, kda_xla
from ray_tpu.util import first_call
from tests import families
from tests.families import l2_err, out_and_grads, rel_err

C, D = 64, 128


def _inputs(chunks, decay, b=2, H=2, seed=None):
    """As ``tests/test_solar_open2.py`` draws them: keys that resemble each
    other (a common part), ``beta`` above 1 on most positions, ``g`` summing
    to ``decay`` times a few a chunk."""
    S = chunks * C
    k = jax.random.split(jax.random.key(chunks if seed is None else seed), 5)
    return (reference.l2norm(jax.random.normal(k[0], (b, S, H, D))) * D ** -.5,
            reference.l2norm(jax.random.normal(k[1], (b, S, H, D)) + 0.5),
            jax.random.normal(k[2], (b, S, H, D)),
            -jax.nn.softplus(jax.random.normal(k[3], (b, S, H, D))) * decay,
            2.0 * jax.nn.sigmoid(jax.random.normal(k[4], (b, S, H)) + 1.0))


def _low(args):
    return tuple(a.astype(jnp.bfloat16) for a in args[:3]) + tuple(args[3:])


@pytest.mark.parametrize("chunks,heads,decay", [(2, 2, 0.3), (3, 3, 0.3),
                                                (2, 3, 10.0), (3, 2, 10.0)])
def test_the_kernels_are_the_recurrence(chunks, heads, decay):
    """Forward and every gradient (q, k, v, g, beta) through ``kda``, which
    takes the kernels at these sizes, against a position-by-position
    ``lax.scan`` in float32, with ``beta`` above 1 and, at ``decay`` 10, a
    ``g`` that sums below -500 inside a chunk (a product of ratios overflows
    at -88): no inf, no nan."""
    args = _inputs(chunks, decay, H=heads)
    assert kda_module.path(args[0].shape, C,
                           jax.sharding.get_abstract_mesh()) == "kernel"
    assert float(jnp.mean(args[4] > 1.0)) > 0.5
    if decay > 1:
        sums = jnp.sum(args[3].reshape(2, chunks, C, heads, D), axis=2)
        assert float(jnp.min(sums)) < -500
    dy = jax.random.normal(jax.random.key(9), args[0].shape)
    with jax.default_matmul_precision("highest"):
        got, grads = out_and_grads(lambda *a: kda(*a, C), args, dy)
        want, grads_ref = out_and_grads(reference.recurrence, args, dy)
    assert np.all(np.isfinite(got))
    assert rel_err(got, want) < 1e-5
    for name, g, g_ref in zip("qkvgb", grads, grads_ref):
        assert np.all(np.isfinite(g)), name
        assert rel_err(g, g_ref) < 1e-4, name


@pytest.mark.parametrize("decay", [0.3, 10.0])
def test_bf16_in_is_within_bf16s_rounding_and_no_further_than_the_xla_form(
        decay):
    """bf16 q, k and v: bf16 products with float32 accumulation.  Output and
    every gradient within bf16's rounding of the float32 recurrence on the
    same (rounded) inputs, and no further from it than the XLA form is: by
    the norm of the difference, since the largest single difference is one
    element's chance in the last rounding (it swings by a third between
    seeds on either side)."""
    low = _low(_inputs(3, decay))
    exact = tuple(a.astype(jnp.float32) for a in low)
    dy = jax.random.normal(jax.random.key(9), low[0].shape, jnp.bfloat16)
    want, grads_ref = out_and_grads(reference.recurrence, exact,
                                     dy.astype(jnp.float32))
    got, grads = out_and_grads(lambda *a: kda(*a, C), low, dy)
    xla, grads_xla = out_and_grads(lambda *a: kda_xla(*a, C), low, dy)
    assert got.dtype == jnp.bfloat16
    for name, g, g_xla, g_ref, a in zip(
            "oqkvgb", (got,) + grads, (xla,) + grads_xla,
            (want,) + grads_ref, (low[0],) + low):
        assert g.dtype == a.dtype, name
        assert rel_err(g, g_ref) < 0.01, name
        assert l2_err(g, g_ref) <= 1.01 * l2_err(g_xla, g_ref), name


@pytest.mark.parametrize("keep_states,heads", [(True, None), (False, None),
                                               (True, 1)],
                         ids=["states-kept", "states-recomputed",
                              "a-head-a-step"])
def test_the_kernels_against_the_xla_form(keep_states, heads):
    """Float32, the same inputs, three chunks: output and gradients of
    ``kda_kernel.scan`` against ``kda_xla``, with the boundary states kept
    by the forward, with the backward running the forward again for them,
    and with one head a grid step."""
    args = _inputs(3, 1.0, seed=11)
    dy = jax.random.normal(jax.random.key(3), args[0].shape)
    with jax.default_matmul_precision("highest"):
        got, grads = out_and_grads(lambda *a: kda_kernel.scan(
            *a, C, heads, keep_states), args, dy)
        want, grads_xla = out_and_grads(lambda *a: kda_xla(*a, C), args, dy)
    assert rel_err(got, want) < 1e-5
    for name, g, g_xla in zip("qkvgb", grads, grads_xla):
        assert rel_err(g, g_xla) < 1e-4, name


def test_the_levels_pairs_tile_the_strict_lower_triangle():
    """Every pair t > s of a chunk belongs to exactly one level's mask, and
    no other pair to any."""
    count = sum(np.asarray(kda_kernel._pairs(C, w), np.int32)
                for w in kda_kernel._levels(C))
    np.testing.assert_array_equal(count, np.tril(np.ones((C, C), int), -1))
    assert kda_kernel._levels(C) == [32, 16, 8, 4, 2, 1]


def test_the_inverses_written_out_backward_is_autodiffs():
    N = jnp.tril(jax.random.normal(jax.random.key(2), (2, 3, C, C)), -1) * 0.3
    dX = jax.random.normal(jax.random.key(4), N.shape)
    with jax.default_matmul_precision("highest"):
        X, pull = jax.vjp(kda_module._unit_lower_inverse, N)
        got = kda_kernel.inverse_backward(X, dX)
    assert rel_err(got, jnp.tril(pull(dX)[0], -1)) < 1e-5


def test_the_state_crosses_chunks_and_starts_a_row_at_zero():
    """Two rows that differ only in their first chunk: their later chunks'
    outputs differ (the state reached them), and a row's first chunk is the
    scan of that chunk alone (no state came in, not the other row's
    either)."""
    a = _inputs(3, 0.05, b=1, seed=5)
    other = (a[0], a[1], a[2].at[:, :C].multiply(-2.0), a[3], a[4])
    both = tuple(jnp.concatenate(pair) for pair in zip(a, other))
    scan = jax.jit(lambda *args: kda(*args, C))
    with jax.default_matmul_precision("highest"):
        o = scan(*both)
        assert float(jnp.max(jnp.abs(o[0, 2 * C:] - o[1, 2 * C:]))) > 1e-3
        # the second row run alone, and its first chunk run alone
        np.testing.assert_allclose(o[1], scan(*other)[0], rtol=1e-6,
                                   atol=1e-6)
        first = tuple(x[:, :C] for x in other)
        np.testing.assert_allclose(o[1, :C], scan(*first)[0], rtol=1e-6,
                                   atol=1e-6)
        assert rel_err(o, reference.recurrence(*both)) < 1e-5
    # position 0's output is ``beta (k . q) v`` of that position alone
    q, k, v, _, beta = both
    start = beta[:, 0, :, None] * jnp.sum(k[:, 0] * q[:, 0], -1,
                                          keepdims=True) * v[:, 0]
    assert rel_err(o[:, 0], start) < 1e-5


#: rows, positions, heads, head_dim, chunk; the mesh's axes
CELL = (1, 8192, 8, 128, 64)
TINY = families.preset("solar_open2")
PLACEMENTS = {
    "the-cell": (CELL, {}, "kernel"),
    "the-cell-on-one-device-of-a-mesh": (CELL, {"data": 1}, "kernel"),
    "tiny": ((2, TINY.seq_len, TINY.kda_heads, TINY.kda_head_dim,
              TINY.kda_chunk), {}, "xla"),
    "the-uncut-heads": ((2, 4096, 64, 128, 64), {}, "kernel"),
    "heads-of-256": ((2, 1024, 4, 256, 64), {}, "kernel"),
    "heads-of-64": ((2, 1024, 16, 64, 64), {}, "xla"),
    "heads-of-192": ((2, 1024, 4, 192, 64), {}, "xla"),
    "chunks-of-16": ((2, 1024, 8, 128, 16), {}, "kernel"),
    "chunks-of-128": ((2, 1024, 8, 128, 128), {}, "kernel"),
    "chunks-of-256": ((2, 1024, 8, 128, 256), {}, "xla"),
    "a-row-of-8": ((2, 8, 8, 128, 64), {}, "xla"),
    "rows-over-data": ((4, 1024, 8, 128, 64), {"data": 4}, "kernel"),
    "rows-over-data-and-fsdp": ((4, 1024, 8, 128, 64),
                                {"data": 2, "fsdp": 2}, "kernel"),
    "heads-over-tensor": ((2, 1024, 8, 128, 64), {"data": 2, "tensor": 2},
                          "kernel"),
    "rows-the-mesh-does-not-divide": ((2, 1024, 8, 128, 64), {"data": 4},
                                      "xla"),
    "heads-the-mesh-does-not-divide": ((2, 1024, 3, 128, 64), {"tensor": 2},
                                       "xla"),
    "positions-over-seq": ((2, 1024, 8, 128, 64), {"seq": 4}, "xla"),
    "an-expert-axis": ((2, 1024, 8, 128, 64), {"data": 2, "expert": 2},
                       "xla"),
}


@pytest.mark.parametrize("name", PLACEMENTS)
def test_which_path_a_call_takes(name):
    """From the shapes and the mesh alone: the kernels where the sizes tile
    and every device of the mesh can scan rows and heads of its own, the XLA
    form everywhere else."""
    (b, S, H, d, chunk), axes, want = PLACEMENTS[name]
    mesh = families.mesh(**axes).abstract_mesh if axes \
        else jax.sharding.get_abstract_mesh()
    assert kda_module.path((b, S, H, d), min(chunk, S), mesh) == want


def test_on_a_mesh_every_device_scans_its_own_rows_and_heads():
    """Four CPU devices, rows over `data` and heads over `tensor`: the
    kernels run inside a ``shard_map`` (a Mosaic call cannot be partitioned)
    and output and gradients are the XLA form's."""
    args = _inputs(2, 1.0, seed=7)
    dy = jax.random.normal(jax.random.key(3), args[0].shape)

    def loss(fn):
        return jax.jit(jax.value_and_grad(
            lambda args: jnp.sum(fn(*args, C) * dy)))

    with jax.default_matmul_precision("highest"):
        want, grads_xla = loss(kda_xla)(args)
        with jax.set_mesh(families.mesh(data=2, tensor=2)), \
                first_call.noting() as notes:
            got, grads = loss(kda)(args)
    assert notes == {"kda_scan_kernel": True, "kda_scan_grid": [1, 1, 2]}
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, g, g_xla in zip("qkvgb", grads, grads_xla):
        assert rel_err(g, g_xla) < 1e-4, name


def test_the_first_call_record_says_which_ran():
    args = _inputs(2, 1.0, H=3)
    with first_call.noting() as notes:
        jax.eval_shape(lambda *a: kda(*a, C), *args)
    assert notes == {"kda_scan_kernel": True, "kda_scan_grid": [2, 1, 2]}
    small = tuple(a[:, :8] for a in args)
    with first_call.noting() as notes:
        jax.eval_shape(lambda *a: kda(*a, 8), *small)
    assert notes == {"kda_scan_kernel": False, "kda_scan_grid": None}
