"""``ops/conv_kernel.py``: the short causal convolution's Mosaic pass under
the interpreter against XLA's form (``mamba2.causal_conv``,
``shortconv.gated_conv``), values and every gradient, at the four call
sites' forms; and the rule that picks the form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from ray_tpu.models import mamba2, shortconv
from ray_tpu.ops import conv_kernel
from ray_tpu.util import first_call

F32, BF16 = jnp.float32, jnp.bfloat16


def _drawn(key, shape, dtype=F32, scale=1.0):
    return (jax.random.normal(jax.random.key(key), shape, F32)
            * scale).astype(dtype)


def _off(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


#: name -> (rows, S, the array's width, the span's offset, the widths that
#: leave, taps, bias, silu, x's dtype, the result's, gated, the blocks
#: (None: the rule's))
FORMS = {
    # kind M: a span inside the projection's output leaves as three arrays
    "bias_silu_k4_inside": (2, 64, 640, 128, (256, 128, 128), 4, True, True,
                            BF16, BF16, False, None),
    # kinds K and G: the whole array, float32 out (q and k) and rounded (v)
    "silu_k4_float32_out": (1, 64, 256, 0, (256,), 4, False, True, BF16, F32,
                            False, None),
    "silu_k4_rounded": (1, 64, 256, 0, (256,), 4, False, True, BF16, BF16,
                        False, None),
    # 2880 is 22.5 lane tiles: a ragged last block (1200 = 2 x 512 + 176)
    "ragged_width": (1, 32, 1200, 0, (1200,), 4, False, True, F32, F32,
                     False, None),
    # narrower than a step and no multiple of 128: the array's own width
    "odd_width": (1, 32, 160, 0, (160,), 4, False, True, F32, F32, False,
                  None),
    # kind C
    "gated_k3": (2, 64, 768, 0, (256,), 3, False, False, BF16, BF16, True,
                 None),
    "gated_k3_float32": (1, 48, 384, 0, (128,), 3, False, False, F32, F32,
                         True, None),
    # blocks of one chunk: every chunk's K - 1 rows cross a block boundary,
    # the first block's are the row's first positions
    "block_boundary": (2, 64, 256, 0, (256,), 4, True, True, F32, F32, False,
                       (16, 128)),
    "block_boundary_gated": (2, 64, 384, 0, (128,), 3, False, False, F32,
                             F32, True, (16, 128)),
    "no_activation": (1, 32, 128, 0, (128,), 2, True, False, F32, F32, False,
                      None),
    # a block of 16 chunks: whole turns of the in-kernel loop (the shorter
    # forms above are steps written out); three blocks of 8 chunks a row
    "turns_and_rest": (1, 256, 256, 0, (256,), 4, True, True, BF16, BF16,
                       False, None),
    "turns_and_rest_gated": (1, 384, 384, 0, (128,), 3, False, False, F32,
                             F32, True, (128, 128)),
}


def forms(offset, widths, act, out_dtype, gated):
    """-> (XLA's form, the kernel's) of a call site: f(x, w, b), a tuple of
    results each (``scripts/conv_pass_sweep.py`` times the same two)."""
    span = sum(widths)

    def xla(x, w, b):
        if gated:
            return (shortconv.gated_conv(x, w),)
        y = mamba2.causal_conv(x[..., offset:offset + span], w, b)
        y = (jax.nn.silu(y) if act else y).astype(out_dtype)
        return tuple(jnp.split(y, list(np.cumsum(widths[:-1])), axis=-1))

    def kernel(x, w, b):
        if gated:
            return (conv_kernel.gated(x, w),)
        return conv_kernel.conv(x, w, b, act=act, out_dtype=out_dtype,
                                offset=offset, widths=widths)

    return xla, kernel


@pytest.mark.parametrize("name", FORMS)
def test_the_kernel_is_xlas_form(name, monkeypatch):
    """Values and the gradients of x (the gated form's three cotangents),
    the taps and the bias, the interpreter's run of the kernel against
    ``causal_conv`` / ``gated_conv``; beyond the span x's gradient is zero;
    a row's first K - 1 positions read zeros, not the row before."""
    (rows, S, full, offset, widths, taps, bias, act, dtype, out_dtype, gated,
     block) = FORMS[name]
    span = full // 3 if gated else sum(widths)
    if block:
        monkeypatch.setattr(conv_kernel, "blocks", lambda *_, **__: block)
    x = _drawn(1, (rows, S, full), dtype)
    w = _drawn(2, (taps, span), F32, 0.5)
    b = _drawn(3, (span,), F32) if bias else None
    xla, kernel = forms(offset, widths, act, out_dtype, gated)
    want, pull_want = jax.vjp(xla, x, w, b)
    got, pull_got = jax.vjp(kernel, x, w, b)
    dys = tuple(_drawn(4 + i, y.shape, y.dtype) for i, y in enumerate(want))
    tol = 1e-5 if dtype == F32 else 1e-2
    assert [y.dtype for y in got] == [y.dtype for y in want]
    assert max(_off(a, b) for a, b in zip(got, want)) < tol
    dwant, dgot = pull_want(dys), pull_got(dys)
    assert [a.dtype for a in jax.tree.leaves(dgot)] \
        == [a.dtype for a in jax.tree.leaves(dwant)]
    assert dgot[0].shape == x.shape and (b is None) == (dgot[2] is None)
    assert max(_off(a, b) for a, b in zip(jax.tree.leaves(dgot),
                                          jax.tree.leaves(dwant))) < tol
    if not gated:
        outside = np.ones(full, bool)
        outside[offset:offset + span] = False
        assert not np.asarray(dgot[0], np.float32)[..., outside].any()


def test_two_rows_do_not_read_each_other():
    """The second row's result is the row's alone: the same as when the row
    stands first, in both forms and both directions."""
    x = _drawn(10, (2, 32, 384))
    w = _drawn(11, (3, 128), F32, 0.5)
    for f in (lambda x: conv_kernel.conv(x[..., :128], w, None, act=True,
                                         out_dtype=F32),
              lambda x: conv_kernel.gated(x, w)):
        both, pull = jax.vjp(f, x)
        alone, pull_alone = jax.vjp(f, x[1:])
        np.testing.assert_array_equal(both[1:], alone)
        dy = _drawn(12, both.shape)
        np.testing.assert_array_equal(pull(dy)[0][1:],
                                      pull_alone(dy[1:])[0])


_ONE = AbstractMesh((1,), ("data",))
_FOUR = AbstractMesh((4,), ("fsdp",))
_NONE = AbstractMesh((), ())
#: the four cells' call sites: (x's shape, taps, offset, widths, gated)
CELLS = {
    "nemotron-ep16-s8192": ((2, 8192, 10304), 4, 4096, (4096, 1024, 1024),
                            False),
    "solar-open2-ep40-tp8": ((1, 8192, 1024), 4, 0, None, False),
    "olmo-hybrid-s8192 q, k": ((1, 8192, 2880), 4, 0, None, False),
    "olmo-hybrid-s8192 v": ((1, 8192, 5760), 4, 0, None, False),
    "lfm2-ep4-s8192": ((2, 8192, 6144), 3, 0, None, True),
}


@pytest.mark.parametrize("cell", CELLS)
def test_the_rule_takes_the_kernel_at_the_cells_shapes(cell, monkeypatch):
    """On the chip with no mesh or one device: the kernel, in blocks that
    divide S and fit the step's bytes; under a mesh, and off the chip,
    XLA's form."""
    shape, taps, offset, widths, gated = CELLS[cell]
    assert conv_kernel.path(shape, taps, _NONE, offset, widths, gated) \
        == "xla"  # this CPU
    monkeypatch.setattr(conv_kernel, "on_chip", lambda: True)
    for mesh, want in ((_NONE, "kernel"), (_ONE, "kernel"), (_FOUR, "xla")):
        assert conv_kernel.path(shape, taps, mesh, offset, widths, gated) \
            == want
    full = shape[2]
    span = widths or ((full // 3,) if gated else (full,))
    rows, lanes = conv_kernel.blocks(shape[1], full, offset, span, 2, gated)
    assert shape[1] % rows == 0 and rows % conv_kernel.ROWS == 0
    assert lanes % conv_kernel.LANES == 0
    if offset or gated:
        assert offset % lanes == 0 and all(w % lanes == 0 for w in span)
    step = (7 * span[0] if gated else 3 * lanes) * 2
    assert rows * step <= conv_kernel.STEP_BYTES


def test_the_rule_refuses_what_the_blocks_cannot_walk(monkeypatch):
    """A span at an offset no lane block divides, positions that are no
    whole chunks, a gate's width of no whole lane tiles, eight taps."""
    monkeypatch.setattr(conv_kernel, "on_chip", lambda: True)
    path = conv_kernel.path
    assert path((2, 128, 640), 4, _NONE, 128, (256, 128)) == "kernel"
    assert path((2, 128, 640), 4, _NONE, 64, (256,)) == "xla"
    assert path((2, 100, 640), 4, _NONE) == "xla"
    assert path((2, 128, 3 * 96), 3, _NONE, gated=True) == "xla"
    assert path((2, 128, 3 * 128), 3, _NONE, gated=True) == "kernel"
    assert path((2, 128, 256), 8, _NONE) == "xla"
    assert path((2, 128, 256), 7, _NONE) == "kernel"


def test_a_call_site_notes_the_form_it_took(monkeypatch):
    """``engaged`` notes ``conv_kernel`` and counts ``conv_calls``; the four
    call sites run the kernel where it says so, with XLA's numbers."""
    x = _drawn(20, (1, 32, 128), BF16)
    w = _drawn(21, (4, 128), F32, 0.5)
    with first_call.noting() as notes:
        want = mamba2.short_conv(x, w, BF16)
        mamba2.short_conv(x, w, F32)
    assert notes == {"conv_kernel": "xla", "conv_calls": 2}
    monkeypatch.setattr(conv_kernel, "on_chip", lambda: True)
    with first_call.noting() as notes:
        got = mamba2.short_conv(x, w, BF16)
    assert notes == {"conv_kernel": "kernel", "conv_calls": 1}
    assert got.dtype == BF16 and want.dtype == F32
    assert _off(got, want.astype(BF16)) < 1e-2


@pytest.mark.parametrize("kind", "MKGC")
def test_a_kinds_mixer_runs_the_kernel_with_xlas_numbers(kind, monkeypatch):
    """Each of the four call sites, through its kind's ``mixer`` on the
    rehearsal preset that holds the kind, in float32: the layer's values and
    every gradient with the kernel engaged are XLA's form's.  (`M`'s preset
    at a state of 64, so that ``xs | B | C`` are whole lane tiles as the
    cell's are.)"""
    from ray_tpu.models import hybrid
    from tests import families

    config = families.float32(families.HOLDER[kind],
                              **({"ssm_state": 64} if kind == "M" else {}))
    module = hybrid.KINDS[kind].module
    axes = module.logical_axes(config)
    blk = jax.tree.map(lambda a: a[0], module.init_params(
        config, jax.random.key(0), 1, 0.02))
    blk = jax.tree.map(  # nothing at a value that hides a term
        lambda a: a + 0.05 * _drawn(30, a.shape) if a.ndim else a, blk)
    x = _drawn(31, (2, 128, config.d_model))

    def run():
        with first_call.noting() as notes:
            out, pull = jax.vjp(
                lambda x, blk: module.mixer(x, blk, config, axes), x, blk)
            return out, pull(_drawn(32, out.shape)), notes["conv_kernel"]

    want, dwant, form = run()
    assert form == "xla"
    monkeypatch.setattr(conv_kernel, "on_chip", lambda: True)
    got, dgot, form = run()
    assert form == "kernel"
    assert _off(got, want) < 1e-5
    assert jax.tree.structure(dgot) == jax.tree.structure(dwant)
    assert max(_off(a, b) for a, b in zip(jax.tree.leaves(dgot),
                                          jax.tree.leaves(dwant))) < 2e-4
