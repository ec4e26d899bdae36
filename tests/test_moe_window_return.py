"""``ops/window_return.py``: a window's rows back to their tokens as a Mosaic
kernel (``models/moe.py:_from_window`` on the chip), in Pallas' interpret mode
against the float32 scatter-add of ``tests/test_moe_combine_layout.py`` and
against the gather it stands in for; the pairing with ``_to_window``; the rule
that picks between the two forms; the lists the kernel's grid walks.  CPU
only: counts and values, no times."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.ops import window_return
from ray_tpu.util import first_call

N, D = 512, 256
SLOTS = (4, 6, 8, 10)


def _f32(a):
    return np.asarray(a, np.float32)


def _scatter_add(rows, inverse, run):
    """``tests/test_moe_combine_layout.py:_scatter_add``: each row of the run
    added, in float32 and in slot order, to the token whose pair it is."""
    first, stop, lead = (int(v) for v in run)
    rows, inverse = _f32(rows), np.asarray(inverse)
    out = np.zeros((inverse.shape[0], rows.shape[1]), np.float32)
    for token, places in enumerate(inverse):
        for at in places:
            if first <= at < stop:
                out[token] += rows[at - (first - lead)]
    return out


def _one_rounding(got, want32):
    got = _f32(got)
    assert np.all(np.isfinite(got))
    assert np.all(np.abs(got - want32) <= 2.0 ** -8 * np.abs(want32))


def _experts(k, E, held, share, seed):
    """(N, k) ids over E experts: a token takes each of its k from the
    ``held`` experts (a range) with probability ``share``."""
    rng = np.random.default_rng(seed)
    lo, hi = held
    rest = np.setdiff1d(np.arange(E), np.arange(lo, hi))
    out = np.empty((N, k), np.int64)
    for token in range(N):
        inside = min(rng.binomial(k, share), hi - lo)
        out[token] = np.concatenate([
            rng.choice(np.arange(lo, hi), inside, replace=False),
            rng.choice(rest, k - inside, replace=False)])
    return out


def _window(experts, E, held, c, run=None, dtype=jnp.bfloat16):
    """-> (rows, pairs, inverse, run) of window ``c`` of the ``held``
    experts' run, cut as ``moe._move_window`` cuts it (or at ``run``); the
    rows of the window outside the run NaN."""
    k = experts.shape[1]
    order, inverse, sizes = (np.asarray(a) for a in moe.sort_pairs(
        jnp.asarray(experts, jnp.int32), E))
    R = moe.window_rows(N * k)
    start, total = int(sizes[:held[0]].sum()), int(sizes[:held[1]].sum())
    first = start + c * R
    first, stop, lead = run or (first, min(first + R, total),
                                max(first + R - N * k, 0))
    rows = np.random.default_rng(k).standard_normal((R, D)).astype(np.float32)
    at = np.arange(R)
    rows[~((at >= lead) & (at < lead + stop - first))] = np.nan
    return (jnp.asarray(rows, dtype),
            jnp.asarray(order[first - lead:first - lead + R], jnp.int32),
            jnp.asarray(inverse, jnp.int32),
            tuple(jnp.int32(v) for v in (first, stop, lead)))


def _zero_one_k(k):
    """Token 0 has all k rows in the run, token 1 none, token 2 one."""
    experts = _experts(k, 16 * k, (0, k), 1 / 16, k)
    experts[0] = np.arange(k)
    experts[1] = np.arange(k, 2 * k)
    experts[2] = np.concatenate([[0], np.arange(k + 1, 2 * k)])
    return _window(experts, 16 * k, (0, k), 0)


#: name -> k -> (rows, pairs, inverse, run)
CASES = {
    # six experts in the order's middle hold less than a window
    "inside": lambda k: _window(_experts(k, 32, (4, 10), 0.1, k), 32,
                                (4, 10), 0),
    # the last experts are held: the second window ends past the order
    "lead": lambda k: _window(_experts(k, 32, (24, 32), 0.18, k), 32,
                              (24, 32), 1),
    "empty": lambda k: _window(_experts(k, 32, (4, 10), 0.1, k), 32, (4, 10),
                               0, run=(N * k // 4,) * 2 + (0,)),
    "full": lambda k: _window(_experts(k, 32, (0, 16), 0.5, k), 32, (0, 16),
                              0),
    # every row of the run on one expert: its tokens rise through the run
    "one-expert": lambda k: _window(_experts(k, 32, (3, 4), 0.1, k), 32,
                                    (3, 4), 0),
    "zero-one-k": _zero_one_k,
}


@pytest.fixture
def kernel(monkeypatch):
    """The kernel is the return here, in interpret mode."""
    monkeypatch.setattr(window_return, "on_chip", lambda: True)


def _fresh(f):
    """jit keeps its traces by the function: one traced before ``on_chip``
    was replaced would answer for the other form."""
    return jax.jit(lambda *args: f(*args))


@pytest.mark.parametrize("k", SLOTS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_float32_scatter_add_and_the_gather(
        monkeypatch, case, k):
    rows, pairs, inverse, run = window = CASES[case](k)
    first, stop, lead = (int(v) for v in run)
    R = rows.shape[0]
    assert {"empty": stop == first, "full": stop - first == R,
            "lead": lead > 0}.get(case, 0 < stop - first < R)
    if case == "zero-one-k":
        inside = ((np.asarray(inverse) >= first)
                  & (np.asarray(inverse) < stop)).sum(axis=1)
        assert tuple(inside[:3]) == (k, 0, 1)
    gathered = _fresh(moe._from_window)(*window)
    monkeypatch.setattr(window_return, "on_chip", lambda: True)
    with first_call.noting() as notes:
        got = _fresh(moe._from_window)(*window)
    assert notes["moe_return"] == {f"{R}x{N}x{k}x{D}": ("kernel", 256)}
    assert got.dtype == rows.dtype and got.shape == (N, D)
    want = _scatter_add(rows, inverse, run)
    _one_rounding(got, want)
    _one_rounding(gathered, want)
    # two roundings of one float32 sum whose terms met in another order
    assert np.all(np.abs(_f32(got) - _f32(gathered))
                  <= 2.0 ** -7 * np.abs(want))
    assert not np.any(_f32(got)[~np.any(want, axis=1)])


@pytest.mark.parametrize("tile", [(64, 128), (128, 256), (8, 128)])
def test_other_tiles_and_blocks_of_columns_return_the_same(
        monkeypatch, kernel, tile):
    """More tiles than chunks and the other way round, and the columns in
    blocks: the lists' every shape."""
    window = CASES["full"](6)
    monkeypatch.setattr(window_return, "tile", lambda *_: tile)
    got = _fresh(moe._from_window)(*window)
    _one_rounding(got, _scatter_add(window[0], *window[2:]))


def test_float32_rows_are_placed_to_the_bit(kernel):
    rows, pairs, inverse, run = CASES["lead"](8)
    rows = jnp.asarray(np.where(np.isnan(_f32(rows)), np.nan,
                                np.random.default_rng(1).standard_normal(
                                    rows.shape)), jnp.float32)
    got = _fresh(moe._from_window)(rows, pairs, inverse, run)
    np.testing.assert_allclose(_f32(got), _scatter_add(rows, inverse, run),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("k", SLOTS)
def test_the_two_moves_stay_each_others_transposes(kernel, k):
    """``jax.vjp`` of ``_to_window`` is the kernel, and the kernel's is
    ``_to_window``."""
    rows, pairs, inverse, run = CASES["lead"](k)
    first, stop, lead = (int(v) for v in run)
    own = slice(lead, lead + stop - first)
    window = (pairs, inverse, run)
    x = jnp.asarray(np.random.default_rng(k + 100).standard_normal((N, D)),
                    jnp.bfloat16)
    g_rows = jnp.where(jnp.isnan(rows), 0, rows)

    moved, back = jax.vjp(lambda x: moe._to_window(x, *window), x)
    with first_call.noting() as notes:
        (gx,) = back(g_rows)
    assert [how for how, _ in notes["moe_return"].values()] == ["kernel"]
    np.testing.assert_array_equal(
        _f32(gx), _f32(_fresh(moe._from_window)(g_rows, *window)))
    _one_rounding(gx, _scatter_add(g_rows, inverse, run))
    (g_back,) = jax.vjp(lambda r: moe._from_window(r, *window), g_rows)[1](x)
    np.testing.assert_array_equal(_f32(g_back)[own], _f32(moved)[own])


def test_the_layer_through_the_kernel_is_the_layer_through_the_gather(
        monkeypatch):
    """A share's layer, values and gradients, both ways (the windows' loop
    hands the kernel its run as traced scalars)."""
    rng = np.random.default_rng(57)
    k, E, H, F = 4, 16, 8, 128
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.bfloat16)
    weights = jnp.asarray(rng.uniform(0.1, 1, (N, k)), jnp.float32)
    experts = jnp.asarray(_experts(k, E, (0, H), 0.5, 3), jnp.int32)
    w_gate, w_up = (jnp.asarray(rng.standard_normal((H, D, F)) / 16,
                                jnp.bfloat16) for _ in range(2))
    w_down = jnp.asarray(rng.standard_normal((H, F, D)) / 16, jnp.bfloat16)

    def loss(x, w_gate, w_up, w_down):
        y, held, moved = moe.expert_mlp(x, weights, experts, w_gate, w_up,
                                        w_down, E)
        return jnp.sum(y.astype(jnp.float32) ** 2), (y, moved)

    def both():
        return jax.jit(lambda *a: jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True)(*a))(
                x, w_gate, w_up, w_down)

    (_, (y_gather, moved)), grads_gather = both()
    assert int(moved) >= 2 * moe.window_rows(N * k)  # two windows and more
    monkeypatch.setattr(window_return, "on_chip", lambda: True)
    with first_call.noting() as notes:
        (_, (y_kernel, _)), grads_kernel = both()
    assert list(notes["moe_return"].values()) == [("kernel", 256)]
    for got, want in zip([y_kernel, *grads_kernel], [y_gather, *grads_gather]):
        got, want = _f32(got), _f32(want)
        assert np.max(np.abs(got - want)) <= 2.0 ** -6 * np.max(np.abs(want))


def _abstract_mesh(**axes):
    return jax.make_mesh(tuple(axes.values()), tuple(axes)).abstract_mesh


#: R, N, D, the mesh's axes, on the chip -> the path, the tile
PATHS = {
    "lfm2-ep4-s8192": (8192, 16384, 2048, {}, True, "kernel", (256, 2048)),
    "solar-open2-ep40-tp8": (8192, 8192, 4096, {}, True, "kernel",
                             (256, 2048)),
    "sdar-ep8-s8192": (16384, 16384, 2048, {}, True, "kernel", (256, 2048)),
    "nemotron-ep16-s8192": (12288, 16384, 2688, {}, True, "kernel",
                            (256, 896)),
    "laguna-ep32-s8192": (10240, 8192, 3072, {}, True, "kernel",
                          (256, 1536)),
    "joyai-ep16-s8192": (8192, 8192, 2048, {}, True, "kernel", (256, 2048)),
    "one-device-mesh": (8192, 16384, 2048, {"data": 1}, True, "kernel",
                        (256, 2048)),
    "off-the-chip": (8192, 16384, 2048, {}, False, "gather", (256, 2048)),
    # no cell runs an expert layer under a mesh (ROADMAP B12)
    "mesh-of-4": (8192, 16384, 2048, {"fsdp": 4}, True, "gather",
                  (256, 2048)),
    "data2.expert2": (8192, 16384, 2048, {"data": 2, "expert": 2}, True,
                      "gather", (256, 2048)),
    "width-2000": (8192, 16384, 2000, {}, True, "gather", None),
    "tokens-no-tile-divides": (1536, 12284, 2048, {}, True, "gather", None),
    # a tiny preset's layer: 256 tokens, k = 4, so a window of 128 rows
    "tiny-preset-d128": (128, 256, 128, {}, True, "kernel", (256, 128)),
    "tiny-preset-d64": (128, 256, 64, {}, True, "gather", None),
    "window-no-chunk-divides": (96, 256, 128, {}, True, "gather", None),
}


@pytest.mark.parametrize("case", sorted(PATHS))
def test_the_path_is_read_from_the_call(monkeypatch, case):
    R, tokens, width, axes, on_chip, want, tile = PATHS[case]
    if on_chip:  # what the described TPU's backend says of itself
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = _abstract_mesh(**axes) if axes \
        else jax.sharding.get_abstract_mesh()
    assert window_return.path(R, tokens, width, mesh) == want
    assert window_return.tile(R, tokens, width) == tile
    if tile:
        T, block = tile
        assert tokens % T == 0 and width % block == 0 and block % 128 == 0
        assert T * block * 4 <= window_return.ACC_BYTES


@pytest.mark.parametrize("inside", [0, 1, 5, 128, 129, 300, 384])
def test_the_items_visit_every_tile_and_every_chunk_of_its_run(inside):
    """The lists against a plain loop: tiles in order, a tile's chunks those
    its rows lie in (one where it has none), the rest repeating the last."""
    R, tokens, T, P = 384, 512, 64, 128
    rng = np.random.default_rng(inside)
    of = np.sort(rng.choice(tokens, inside)) if inside else np.zeros(0, int)
    sorted_tokens = np.concatenate([of, np.full(R - inside, tokens)])
    tiles, chunks, count = (np.asarray(a) for a in window_return.items(
        jnp.asarray(sorted_tokens, jnp.int32), tokens, T, P))
    want = []
    for tile in range(tokens // T):
        own = np.flatnonzero(sorted_tokens // T == tile)
        before = int((sorted_tokens < tile * T).sum())
        lo = min(before // P, R // P - 1)
        hi = own[-1] // P if len(own) else lo
        want += [(tile, c) for c in range(lo, hi + 1)]
    assert int(count[0]) == len(want) <= tokens // T + R // P == len(tiles)
    want += [want[-1]] * (len(tiles) - len(want))
    assert list(zip(tiles.tolist(), chunks.tolist())) == want


def test_a_share_step_lowers_for_the_tpu_with_the_kernel(monkeypatch):
    """Lowering for the TPU needs no TPU: a layer that holds a share, with
    its backward, holds the kernel's call twice (the forward's return and,
    in the backward's pass, ``_to_window``'s transpose: that pass's own
    return nothing reads, and it is dropped) and no (k, N, D) gather; the
    first-call notes name the path."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    k, E, H, F = 4, 16, 4, 128
    x = jax.ShapeDtypeStruct((N, D), jnp.bfloat16)
    weights = jax.ShapeDtypeStruct((N, k), jnp.float32)
    experts = jax.ShapeDtypeStruct((N, k), jnp.int32)
    w_in = jax.ShapeDtypeStruct((H, D, F), jnp.bfloat16)
    w_down = jax.ShapeDtypeStruct((H, F, D), jnp.bfloat16)

    def loss(x, weights, experts, w_gate, w_up, w_down):
        y, _, _ = moe.expert_mlp(x, weights, experts, w_gate, w_up, w_down, E)
        return jnp.sum(y.astype(jnp.float32))

    with first_call.noting() as notes:
        text = jax.jit(jax.grad(loss, argnums=(0, 3))).trace(
            x, weights, experts, w_in, w_in, w_down).lower(
                lowering_platforms=("tpu",)).as_text()
    R = moe.window_rows(N * k)
    assert notes["moe_return"] == {f"{R}x{N}x{k}x{D}": ("kernel", 256)}
    # the return is one jitted function of the step, lowered once ...
    assert len([line for line in text.splitlines() if "@tpu_custom_call"
                in line and "moe_window_return" in line]) == 1
    # ... and called from each loop's pass
    assert len(re.findall(r"call @_sorted_and_placed\w*\(", text)) == 2
    assert f"tensor<{k}x{N}x{D}xbf16>" not in text
    assert f"tensor<{k * N}x{D}xbf16>" not in text
