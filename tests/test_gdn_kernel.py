"""The gated-delta-net scan's Pallas kernels (``ops/gdn_kernel.py``) in
interpret mode, at the published widths (keys of 96 under values of 192, off
every lane tile) and at one that lies on them (128 under 128), chunks of 16
and 64, two rows, three heads: against the float32 recurrence position by
position (``benchmarks/reference/olmo_hybrid.py:recurrence``, at the
tolerances ``tests/test_gdn.py`` holds the XLA form to), against the XLA form
on the same inputs, and which of the two a call takes (``ops.gdn.path``).
That the cell's shape compiles for the v5e is in
``tests/test_attention_blocks.py`` beside the other kernels' compiles (one
file loads the TPU's compiler).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference.olmo_hybrid import l2norm, recurrence
from ray_tpu.ops import gdn as gdn_module
from ray_tpu.ops import gdn_kernel
from ray_tpu.ops.gdn import gdn, gdn_xla
from ray_tpu.util import first_call
from tests import families
from tests.families import l2_err, out_and_grads, rel_err

S = 128
WIDTHS = [(96, 192), (128, 128)]


def _inputs(case, dk=96, dv=192, b=2, H=3, S=S, seed=0, chunk=64):
    """(q, k, v, g, beta) in float32, as ``tests/test_gdn.py`` draws them:
    unit keys that share a part, queries scaled by dk^-1/2."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = l2norm(jax.random.normal(ks[0], (b, S, H, dk))) * dk ** -0.5
    k = l2norm(jax.random.normal(ks[1], (b, S, H, dk)) + 0.5)
    v = jax.random.normal(ks[2], (b, S, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, S, H)) - 2.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, S, H)))
    if case == "beta-near-2":
        # the reflection's edge: an eigenvalue of I - beta k k^T near -1
        beta = 2.0 - 1e-3 * jax.random.uniform(ks[4], (b, S, H))
    if case == "strong-decay":
        # 6 to 10 a position: under -88 inside a chunk of 16, -380 in one
        # of 64 (a product of ratios overflows at -88)
        g = -(6.0 + 4.0 * jax.random.uniform(ks[3], (b, S, H)))
        sums = jnp.sum(g.reshape(b, S // chunk, chunk, H), axis=2)
        assert float(jnp.max(sums)) < -88
    return q, k, v, g, beta


def _low(args):
    return tuple(a.astype(jnp.bfloat16) for a in args[:3]) + tuple(args[3:])


def _takes_the_kernels(args, chunk):
    return gdn_module.path(args[0].shape, args[2].shape, chunk,
                           jax.sharding.get_abstract_mesh()) == "kernel"


@pytest.mark.parametrize("case", ["plain", "beta-near-2", "strong-decay"])
@pytest.mark.parametrize("chunk", [16, 64], ids=["c16", "c64"])
@pytest.mark.parametrize("dk,dv", WIDTHS, ids=["96-under-192", "128s"])
def test_the_kernels_are_the_recurrence(dk, dv, chunk, case):
    """Float32: forward and every gradient (q, k, v, g, beta) through
    ``gdn``, which takes the kernels at these sizes, against a
    position-by-position ``lax.scan``, with ``beta`` near 2 and with a ``g``
    that sums far below -88 inside a chunk: no inf, no nan."""
    args = _inputs(case, dk, dv, chunk=chunk)
    assert _takes_the_kernels(args, chunk)
    dy = jax.random.normal(jax.random.key(9), args[2].shape)
    with jax.default_matmul_precision("highest"):
        got, grads = out_and_grads(lambda *a: gdn(*a, chunk), args, dy)
        want, grads_ref = out_and_grads(recurrence, args, dy)
    assert got.shape == args[2].shape and np.all(np.isfinite(got))
    assert rel_err(got, want) < 2e-4
    for name, g, g_ref in zip("qkvgb", grads, grads_ref):
        assert np.all(np.isfinite(g)), name
        assert rel_err(g, g_ref) < 2e-4, name


@pytest.mark.parametrize("case", ["plain", "beta-near-2", "strong-decay"])
@pytest.mark.parametrize("chunk", [16, 64], ids=["c16", "c64"])
@pytest.mark.parametrize("dk,dv", WIDTHS, ids=["96-under-192", "128s"])
def test_bf16_in_is_within_bf16s_rounding_and_no_further_than_the_xla_form(
        dk, dv, chunk, case):
    """bf16 q, k and v: bf16 products with float32 accumulation.  Output and
    every gradient within bf16's rounding of the float32 recurrence on the
    same (rounded) inputs, and no further from it than the XLA form is, by
    the norm of the difference (the largest single difference is one
    element's chance in the last rounding)."""
    low = _low(_inputs(case, dk, dv, chunk=chunk))
    exact = tuple(a.astype(jnp.float32) for a in low)
    dy = jax.random.normal(jax.random.key(9), low[2].shape, jnp.bfloat16)
    want, grads_ref = out_and_grads(recurrence, exact,
                                     dy.astype(jnp.float32))
    got, grads = out_and_grads(lambda *a: gdn(*a, chunk), low, dy)
    xla, grads_xla = out_and_grads(lambda *a: gdn_xla(*a, chunk), low, dy)
    assert got.dtype == jnp.bfloat16
    for name, g, g_xla, g_ref, a in zip(
            "oqkvgb", (got,) + grads, (xla,) + grads_xla,
            (want,) + grads_ref, (low[2],) + low):
        assert g.dtype == a.dtype and g.shape == a.shape, name
        assert np.all(np.isfinite(np.asarray(g, np.float32))), name
        assert rel_err(g, g_ref) < 4e-2, name
        assert l2_err(g, g_ref) <= 1.02 * l2_err(g_xla, g_ref), name


#: (keys, values, heads, heads a grid step; None: the rule's).  The rule's
#: own at every width (all three heads of 96 under 192 and of 64 under 128,
#: one of 128s), then blocks of heads the rule would not pick, where a
#: step's columns are whole lane tiles and the block index walks them: a
#: head a step, and two of four
LAYOUTS = {
    "96-under-192": (96, 192, 3, None), "128s": (128, 128, 3, None),
    "64-under-128": (64, 128, 3, None),
    "128s-a-head-a-step": (128, 128, 3, 1),
    "64-under-128-two-of-four-heads-a-step": (64, 128, 4, 2),
}


@pytest.mark.parametrize("chunk", [16, 64], ids=["c16", "c64"])
@pytest.mark.parametrize("name", LAYOUTS)
def test_the_kernels_against_the_xla_form(name, chunk):
    """Float32, the same inputs, three chunks of 64 or twelve of 16: output
    and gradients of ``gdn_kernel.scan`` against ``gdn_xla``, at the heads
    a grid step the rule gives and at others."""
    dk, dv, H, heads = LAYOUTS[name]
    args = _inputs("plain", dk, dv, H=H, S=192, seed=11)
    dy = jax.random.normal(jax.random.key(3), args[2].shape)
    with jax.default_matmul_precision("highest"):
        got, grads = out_and_grads(lambda *a: gdn_kernel.scan(
            *a, chunk, heads), args, dy)
        want, grads_xla = out_and_grads(lambda *a: gdn_xla(*a, chunk), args,
                                         dy)
    assert rel_err(got, want) < 1e-5
    for name, g, g_xla in zip("qkvgb", grads, grads_xla):
        assert rel_err(g, g_xla) < 1e-4, name


def test_the_state_crosses_chunks_and_starts_a_row_at_zero():
    """Two rows that differ only in their first chunk: their later chunks'
    outputs differ (the state reached them), and a row's first chunk is the
    scan of that chunk alone (no state came in, not the other row's
    either)."""
    C = 64
    q, k, v, g, beta = (x[:1] for x in _inputs("plain", S=3 * C, seed=5))
    a = (q, k, v, 0.05 * g, beta)  # a decay that lets a state last
    other = (a[0], a[1], a[2].at[:, :C].multiply(-2.0), a[3], a[4])
    both = tuple(jnp.concatenate(pair) for pair in zip(a, other))
    scan = jax.jit(lambda *args: gdn(*args, C))
    with jax.default_matmul_precision("highest"):
        o = scan(*both)
        assert float(jnp.max(jnp.abs(o[0, 2 * C:] - o[1, 2 * C:]))) > 1e-3
        # the second row run alone, and its first chunk run alone
        np.testing.assert_allclose(o[1], scan(*other)[0], rtol=1e-6,
                                   atol=1e-6)
        first = tuple(x[:, :C] for x in other)
        np.testing.assert_allclose(o[1, :C], scan(*first)[0], rtol=1e-6,
                                   atol=1e-6)
        assert rel_err(o, recurrence(*both)) < 1e-5
    # position 0's output is ``beta (k . q) v`` of that position alone
    q, k, v, _, beta = both
    start = beta[:, 0, :, None] * jnp.sum(k[:, 0] * q[:, 0], -1,
                                          keepdims=True) * v[:, 0]
    assert rel_err(o[:, 0], start) < 1e-5


@pytest.mark.parametrize("H,dk,dv,want", [
    (30, 96, 192, 30),    # no divisor's keys fill lane tiles: all
    (32, 128, 128, 8), (16, 128, 256, 8),
    (6, 64, 128, 6), (4, 96, 192, 4), (3, 96, 192, 3),
    (7, 128, 128, 7),
    (9, 128, 128, 3),     # the largest divisor up to eight, not eight
    (12, 64, 128, 6),     # of 2, 4 and 6, whose keys fill tiles, the most
    (8, 96, 192, 8),      # keys of 96 fill tiles four heads at a time
    (30, 128, 128, 6)])   # the cell's heads at keys that fill a tile each
def test_heads_a_grid_step(H, dk, dv, want):
    assert gdn_kernel.heads_a_step(H, dk, dv) == want


#: rows, positions, heads, a head's keys, its values, chunk; the mesh's axes
CELL = (1, 8192, 30, 96, 192, 64)
TINY = families.preset("olmo_hybrid")
PLACEMENTS = {
    "the-cell": (CELL, {}, "kernel"),
    "the-cell-on-one-device-of-a-mesh": (CELL, {"data": 1}, "kernel"),
    "tiny": ((2, TINY.seq_len, TINY.gdn_heads, TINY.gdn_key_dim,
              TINY.gdn_value_dim, TINY.gdn_chunk), {}, "xla"),
    "qwen3-nexts-128s": ((2, 4096, 32, 128, 128, 64), {}, "kernel"),
    "128-under-256": ((2, 1024, 16, 128, 256, 64), {}, "kernel"),
    "64-under-128": ((2, 1024, 6, 64, 128, 64), {}, "kernel"),
    "keys-of-32": ((2, 1024, 8, 32, 128, 64), {}, "xla"),
    "keys-of-100": ((2, 1024, 8, 100, 192, 64), {}, "xla"),
    "ninety-heads-whose-blocks-outgrow-vmem": ((1, 1024, 90, 96, 192, 64),
                                               {}, "xla"),
    "chunks-of-16": ((2, 1024, 30, 96, 192, 16), {}, "kernel"),
    "chunks-of-128": ((2, 1024, 30, 96, 192, 128), {}, "kernel"),
    "chunks-of-256": ((2, 1024, 30, 96, 192, 256), {}, "xla"),
    "a-row-of-8": ((2, 8, 30, 96, 192, 64), {}, "xla"),
    "rows-over-data": ((4, 1024, 30, 96, 192, 64), {"data": 4}, "kernel"),
    "rows-over-data-and-fsdp": ((4, 1024, 30, 96, 192, 64),
                                {"data": 2, "fsdp": 2}, "kernel"),
    "heads-over-tensor": ((2, 1024, 30, 96, 192, 64),
                          {"data": 2, "tensor": 2}, "kernel"),
    "sixty-heads-over-tensor": ((2, 1024, 60, 96, 192, 64),
                                {"data": 2, "tensor": 2}, "kernel"),
    "rows-the-mesh-does-not-divide": ((2, 1024, 30, 96, 192, 64),
                                      {"data": 4}, "xla"),
    "heads-the-mesh-does-not-divide": ((2, 1024, 30, 96, 192, 64),
                                       {"tensor": 4}, "xla"),
    "positions-over-seq": ((2, 1024, 30, 96, 192, 64), {"seq": 4}, "xla"),
    "an-expert-axis": ((2, 1024, 30, 96, 192, 64), {"data": 2, "expert": 2},
                       "xla"),
}


@pytest.mark.parametrize("name", PLACEMENTS)
def test_which_path_a_call_takes(name):
    """From the shapes and the mesh alone: the kernels where the widths and
    the chunk are ones they were compiled for, a chip's heads' blocks fit
    its VMEM and every device of the mesh can scan rows and heads of its
    own; the XLA form everywhere else."""
    (b, S, H, dk, dv, chunk), axes, want = PLACEMENTS[name]
    mesh = families.mesh(**axes).abstract_mesh if axes \
        else jax.sharding.get_abstract_mesh()
    assert gdn_module.path((b, S, H, dk), (b, S, H, dv), min(chunk, S),
                           mesh) == want


def test_on_a_mesh_every_device_scans_its_own_rows_and_heads():
    """Four CPU devices, rows over `data` and heads over `tensor`: the
    kernels run inside a ``shard_map`` (a Mosaic call cannot be partitioned)
    and output and gradients are the XLA form's."""
    args = _inputs("plain", H=4, seed=7)
    dy = jax.random.normal(jax.random.key(3), args[2].shape)

    def loss(fn):
        return jax.jit(jax.value_and_grad(
            lambda args: jnp.sum(fn(*args, 64) * dy)))

    with jax.default_matmul_precision("highest"):
        want, grads_xla = loss(gdn_xla)(args)
        with jax.set_mesh(families.mesh(data=2, tensor=2)), \
                first_call.noting() as notes:
            got, grads = loss(gdn)(args)
    assert notes == {"gdn_scan_kernel": True, "gdn_scan_grid": [1, 1, 2]}
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, g, g_xla in zip("qkvgb", grads, grads_xla):
        assert rel_err(g, g_xla) < 1e-4, name


def test_the_first_call_record_says_which_ran():
    args = _inputs("plain")
    with first_call.noting() as notes:
        jax.eval_shape(lambda *a: gdn(*a, 64), *args)
    assert notes == {"gdn_scan_kernel": True, "gdn_scan_grid": [2, 1, 2]}
    small = tuple(a[:, :8] for a in args)
    with first_call.noting() as notes:
        jax.eval_shape(lambda *a: gdn(*a, 8), *small)
    assert notes == {"gdn_scan_kernel": False, "gdn_scan_grid": None}
    narrow = _inputs("plain", dk=12, dv=24)
    with first_call.noting() as notes:
        jax.eval_shape(lambda *a: gdn(*a, 64), *narrow)
    assert notes == {"gdn_scan_kernel": False, "gdn_scan_grid": None}


def test_the_layer_takes_the_kernels_at_the_published_widths():
    """``models/gdn.py``'s mixer at keys of 96 under values of 192 notes the
    kernels in the first-call record; the tiny preset's narrow heads the XLA
    form."""
    import dataclasses

    from ray_tpu.models import gdn as layer

    for config, want in (
            (dataclasses.replace(TINY, gdn_heads=2, gdn_key_dim=96,
                                 gdn_value_dim=192, gdn_chunk=64), True),
            (TINY, False)):
        blk = jax.eval_shape(lambda c=config: jax.tree.map(
            lambda a: a[0], layer.init_params(c, jax.random.key(0), 1, 0.02)))
        x = jax.ShapeDtypeStruct((2, 128, config.d_model), config.dtype)
        with first_call.noting() as notes:
            jax.eval_shape(lambda x, blk, c=config: layer.mixer(
                x, blk, c, layer.logical_axes(c)), x, blk)
        assert notes["gdn_scan_kernel"] is want
