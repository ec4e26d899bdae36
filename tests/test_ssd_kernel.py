"""The Mamba-2 scan's Pallas kernels (``ops/ssd_kernel.py``) in interpret
mode, at sizes that lie on the chip's tiles (chunks of 128, heads of 64, a
state of 128, two groups of two heads, two rows): against the float32
recurrence position by position (``benchmarks/reference``, at the tolerances
``tests/test_nemotron_h.py`` holds the XLA form to), against the XLA form on
the same inputs, and which of the two a call takes (``ops.ssd.path``).  That
the cell's shape compiles for the v5e is in ``tests/test_attention_blocks.py``
beside the other kernels' compiles (one file loads the TPU's compiler).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h as reference
from ray_tpu.ops import ssd as ssd_module
from ray_tpu.ops import ssd_kernel
from ray_tpu.ops.ssd import ssd, ssd_xla
from ray_tpu.util import first_call
from tests import families
from tests.families import rel_err

Q = 128


def _inputs(chunks, b=2, H=4, P=64, G=2, N=128, seed=None):
    S = chunks * Q
    k = jax.random.split(jax.random.key(chunks if seed is None else seed), 6)
    return dict(
        x=jax.random.normal(k[0], (b, S, H, P)),
        # delta A adds up to under -200 a chunk of 128: exp of the
        # cumulative sum itself underflows, its reciprocal overflows
        dt=jax.random.normal(k[1], (b, S, H)) - 1.0,
        A_log=jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.0),
        B=jax.random.normal(k[3], (b, S, G, N)),
        C=jax.random.normal(k[4], (b, S, G, N)),
        D=jax.random.normal(k[5], (H,)))


def _run(fn, a):
    return fn(a["x"], jax.nn.softplus(a["dt"]), -jnp.exp(a["A_log"]),
              a["B"], a["C"], a["D"], Q)


def _position_by_position(a):
    J = a["x"].shape[2] // a["B"].shape[2]
    return reference.recurrence(
        a["x"], jax.nn.softplus(a["dt"]), -jnp.exp(a["A_log"]),
        jnp.repeat(a["B"], J, axis=2), jnp.repeat(a["C"], J, axis=2), a["D"])


def _low(a):
    return dict(a, **{k: a[k].astype(jnp.bfloat16) for k in ("x", "B", "C")})


@pytest.mark.parametrize("chunks", [3, 5])
def test_the_kernels_are_the_recurrence(chunks):
    """Forward and every gradient (x, dt, A_log, B, C, D) through ``ssd``,
    which takes the kernels at these sizes, against a position-by-position
    ``lax.scan`` in float32."""
    a = _inputs(chunks)
    assert ssd_module.path(a["x"].shape, a["B"].shape, Q,
                           jax.sharding.get_abstract_mesh()) == "kernel"
    decay = jax.nn.softplus(a["dt"]) * -jnp.exp(a["A_log"])
    assert float(jnp.min(jnp.sum(decay.reshape(2, chunks, Q, -1),
                                 axis=2))) < -200  # exp(200) is no float32
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda a: _run(ssd, a), a)
        want, vjp_ref = jax.vjp(_position_by_position, a)
        assert rel_err(got, want) < 1e-5
        dy = jax.random.normal(jax.random.key(9), want.shape)
        (grads,), (grads_ref,) = vjp(dy), vjp_ref(dy)
    for name in a:
        assert np.all(np.isfinite(grads[name])), name
        assert rel_err(grads[name], grads_ref[name]) < 1e-3, name


def test_bf16_in_is_within_bf16s_rounding_and_no_further_than_the_xla_form():
    """bf16 x, B and C: bf16 products with float32 accumulation.  Output and
    every gradient within bf16's rounding of the float32 recurrence on the
    same (rounded) inputs, and no further from it than the XLA form is: the
    same operands are rounded, the cotangents of the decays are not."""
    low = _low(_inputs(3))
    exact = {k: v.astype(jnp.float32) for k, v in low.items()}
    want, vjp_ref = jax.vjp(_position_by_position, exact)
    got, vjp = jax.vjp(lambda a: _run(ssd, a), low)
    xla, vjp_xla = jax.vjp(lambda a: _run(ssd_xla, a), low)
    assert got.dtype == jnp.bfloat16
    assert rel_err(got, want) < 0.01
    assert rel_err(got, want) <= 1.1 * rel_err(xla, want)
    dy = jax.random.normal(jax.random.key(9), want.shape, jnp.bfloat16)
    (grads,), (grads_xla,) = vjp(dy), vjp_xla(dy)
    (grads_ref,) = vjp_ref(dy.astype(jnp.float32))
    for name in low:
        assert grads[name].dtype == low[name].dtype, name
        err = rel_err(grads[name], grads_ref[name])
        assert err < 0.01, name
        assert err <= 1.1 * rel_err(grads_xla[name], grads_ref[name]) \
            + 1e-6, name


@pytest.mark.parametrize("keep_states", [True, False],
                         ids=["states-kept", "states-recomputed"])
def test_the_kernels_against_the_xla_form(keep_states):
    """Float32, the same inputs, five chunks: output and gradients of
    ``ssd_kernel.scan`` against ``ssd_xla``, with the boundary states kept
    by the forward and with the backward running the forward again for
    them."""
    a = _inputs(5, seed=11)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda a: _run(
            lambda *args: ssd_kernel.scan(*args, keep_states), a), a)
        want, vjp_xla = jax.vjp(lambda a: _run(ssd_xla, a), a)
        dy = jax.random.normal(jax.random.key(3), want.shape)
        (grads,), (grads_xla,) = vjp(dy), vjp_xla(dy)
    assert rel_err(got, want) < 1e-5
    for name in a:
        assert rel_err(grads[name], grads_xla[name]) < 1e-3, name


def test_the_state_crosses_chunks_and_starts_a_row_at_zero():
    """Two rows that differ only in their first chunk: their later chunks'
    outputs differ (the state reached them), and a row's first chunk is the
    scan of that chunk alone (no state came in, not the other row's
    either)."""
    a = _inputs(3, b=1, seed=5)
    other = dict(a, x=a["x"].at[:, :Q].multiply(-2.0))
    both = {k: (jnp.concatenate([a[k], other[k]]) if a[k].ndim > 1 else a[k])
            for k in a}
    y = _run(ssd, both)
    assert float(jnp.max(jnp.abs(y[0, Q:] - y[1, Q:]))) > 1e-2
    # the second row run alone, and its first chunk run alone
    alone = _run(ssd, other)
    np.testing.assert_allclose(y[1], alone[0], rtol=1e-6, atol=1e-6)
    first = {k: (other[k][:, :Q] if other[k].ndim > 1 else other[k])
             for k in other}
    np.testing.assert_allclose(y[1, :Q], _run(ssd, first)[0], rtol=1e-6,
                               atol=1e-6)
    # with slow decays the whole row depends on its first chunk
    slow = dict(both, dt=both["dt"] - 6.0)
    y = _run(ssd, slow)
    assert float(jnp.max(jnp.abs(y[0, 2 * Q:] - y[1, 2 * Q:]))) > 1e-2
    assert rel_err(y, _position_by_position(slow)) < 1e-5


#: rows, positions, heads, head_dim, groups, state, chunk; the mesh's axes
CELL = (2, 8192, 64, 64, 8, 128, 128)
TINY = families.preset("nemotron_h")
PLACEMENTS = {
    "the-cell": (CELL, {}, "kernel"),
    "the-cell-on-one-device-of-a-mesh": (CELL, {"data": 1}, "kernel"),
    "tiny": ((2, TINY.seq_len, TINY.ssm_heads, TINY.ssm_head_dim,
              TINY.ssm_groups, TINY.ssm_state, TINY.ssm_chunk), {}, "xla"),
    "a-row-shorter-than-a-chunk": ((2, 64, 64, 64, 8, 128, 64), {}, "xla"),
    "heads-of-128": ((2, 1024, 16, 128, 8, 128, 256), {}, "kernel"),
    "heads-of-48": ((2, 1024, 64, 48, 8, 128, 128), {}, "xla"),
    "one-head-of-64-a-group": ((2, 1024, 8, 64, 8, 128, 128), {}, "xla"),
    "a-state-of-64": ((2, 1024, 64, 64, 8, 64, 128), {}, "xla"),
    "rows-over-data": ((4, 1024, 64, 64, 8, 128, 128), {"data": 4},
                       "kernel"),
    "rows-over-data-and-fsdp": ((4, 1024, 64, 64, 8, 128, 128),
                                {"data": 2, "fsdp": 2}, "kernel"),
    "groups-over-tensor": ((2, 1024, 64, 64, 8, 128, 128),
                           {"data": 2, "tensor": 2}, "kernel"),
    "rows-the-mesh-does-not-divide": ((2, 1024, 64, 64, 8, 128, 128),
                                      {"data": 4}, "xla"),
    "groups-the-mesh-does-not-divide": ((2, 1024, 12, 64, 3, 128, 128),
                                        {"tensor": 2}, "xla"),
    "positions-over-seq": ((2, 1024, 64, 64, 8, 128, 128), {"seq": 4},
                           "xla"),
    "an-expert-axis": ((2, 1024, 64, 64, 8, 128, 128),
                       {"data": 2, "expert": 2}, "xla"),
}


@pytest.mark.parametrize("name", PLACEMENTS)
def test_which_path_a_call_takes(name):
    """From the shapes and the mesh alone: the kernels where the sizes tile
    and every device of the mesh can scan rows and groups of its own, the
    XLA form everywhere else."""
    (b, S, H, P, G, N, chunk), axes, want = PLACEMENTS[name]
    mesh = families.mesh(**axes).abstract_mesh if axes \
        else jax.sharding.get_abstract_mesh()
    assert ssd_module.path((b, S, H, P), (b, S, G, N), min(chunk, S),
                           mesh) == want


def test_on_a_mesh_every_device_scans_its_own_rows_and_groups():
    """Four CPU devices, rows over `data` and groups over `tensor`: the
    kernels run inside a ``shard_map`` (a Mosaic call cannot be partitioned)
    and output and gradients are the XLA form's, A's and D's gradients
    summed over the rows' shards."""
    a = _inputs(2, b=2, seed=7)
    dy = jax.random.normal(jax.random.key(3), a["x"].shape)

    def loss(fn):
        return jax.jit(jax.value_and_grad(
            lambda a: jnp.sum(_run(fn, a) * dy)))

    with jax.default_matmul_precision("highest"):
        want, grads_xla = loss(ssd_xla)(a)
        with jax.set_mesh(families.mesh(data=2, tensor=2)), \
                first_call.noting() as notes:
            got, grads = loss(ssd)(a)
    assert notes == {"ssm_scan_kernel": True, "ssm_scan_grid": [1, 1, 2]}
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name in a:
        assert rel_err(grads[name], grads_xla[name]) < 1e-3, name


def test_the_first_call_record_says_which_ran():
    a = _inputs(2)
    with first_call.noting() as notes:
        jax.eval_shape(lambda a: _run(ssd, a), a)
    assert notes == {"ssm_scan_kernel": True, "ssm_scan_grid": [2, 2, 2]}
    small = {k: (v[:, :64] if v.ndim > 1 else v) for k, v in a.items()}
    with first_call.noting() as notes:
        jax.eval_shape(lambda a: ssd(
            a["x"], a["dt"], a["A_log"], a["B"], a["C"], a["D"], 64), small)
    assert notes == {"ssm_scan_kernel": False, "ssm_scan_grid": None}
