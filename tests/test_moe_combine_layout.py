"""The window's combine (``models/moe.py``: ``_from_window``) sums a token's
k slots over the leading axis of what it gathers: the numbers against a plain
float32 scatter-add, the pairing with ``_to_window`` as each other's
transposes, and, compiled for a described v5e, that no (N, k, D) array is
left for the layout to pad where k is no multiple of the sublane tile (6 and
10 of the benchmark's cells; ``PERF.md``, PR 55); and, where the described
chip's backend answers, that the six held-share cells' shapes compile to the
kernel of ``ops/window_return.py`` with no array of all k x N slots left
(PR 57).  CPU only: counts, no times.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe

N, D = 32, 16
SLOTS = (6, 8, 10)


def _case(k):
    """A window at the order's end (``lead`` 5) whose run starts 5 rows and
    stops 3 rows inside it; token 0 has all k slots in the run, token 1 none,
    token 2 one.  -> (rows, pairs, inverse, run), the rows of the window
    outside the run NaN."""
    rng = np.random.default_rng(k)
    R, lead = moe.window_rows(N * k), 5
    first = N * k - R + lead
    stop = N * k - 3
    run, rest = np.arange(first, stop), np.arange(first)
    rest = np.concatenate([rest, np.arange(stop, N * k)])
    inverse = np.empty((N, k), np.int64)
    inverse[0] = run[:k]
    inverse[1] = rest[:k]
    inverse[2] = np.concatenate([run[k:k + 1], rest[k:2 * k - 1]])
    inverse[3:] = rng.permutation(np.concatenate(
        [run[k + 1:], rest[2 * k - 1:]])).reshape(N - 3, k)
    for token in range(3):
        inverse[token] = rng.permutation(inverse[token])
    order = np.argsort(inverse.reshape(-1))
    rows = rng.standard_normal((R, D)).astype(np.float32)
    rows[:lead] = rows[R - 3:] = np.nan
    inside = ((inverse >= first) & (inverse < stop)).sum(axis=1)
    assert (inside[0], inside[1], inside[2]) == (k, 0, 1)
    return (jnp.asarray(rows, jnp.bfloat16),
            jnp.asarray(order[first - lead:], jnp.int32),
            jnp.asarray(inverse, jnp.int32),
            tuple(jnp.int32(v) for v in (first, stop, lead)))


def _f32(a):
    return np.asarray(a, np.float32)


def _scatter_add(rows, inverse, run):
    """The reference: each row of the run added, in float32 and in slot
    order, to the token whose pair it is.  -> (N, D) float32."""
    first, stop, lead = (int(v) for v in run)
    rows, inverse = _f32(rows), np.asarray(inverse)
    out = np.zeros((inverse.shape[0], rows.shape[1]), np.float32)
    for token, places in enumerate(inverse):
        for at in places:
            if first <= at < stop:
                out[token] += rows[at - (first - lead)]
    return out


def _one_rounding(got, want32):
    """``got`` (bf16) is ``want32`` (float32) rounded once: within half a
    bf16 step of it, and exactly zero where it is."""
    got = _f32(got)
    assert np.all(np.isfinite(got))
    assert np.all(np.abs(got - want32) <= 2.0 ** -8 * np.abs(want32))


@pytest.mark.parametrize("k", SLOTS)
def test_the_combine_is_the_float32_scatter_add(k):
    rows, pairs, inverse, run = _case(k)
    got = jax.jit(moe._from_window)(rows, pairs, inverse, run)
    assert got.dtype == rows.dtype and got.shape == (N, D)
    _one_rounding(got, _scatter_add(rows, inverse, run))
    assert not np.any(_f32(got[1]))


@pytest.mark.parametrize("k", SLOTS)
def test_the_two_moves_are_each_others_transposes(k):
    """``jax.vjp`` of either is the other: the gather's is the float32
    scatter-add, and the combine's is, on the rows of the run, what
    differentiating the plain function gives."""
    rows, pairs, inverse, run = _case(k)
    first, stop, lead = (int(v) for v in run)
    own = slice(lead, lead + stop - first)
    window = (pairs, inverse, run)
    x = jnp.asarray(np.random.default_rng(k + 100).standard_normal((N, D)),
                    jnp.bfloat16)
    g_rows = jnp.where(jnp.isnan(rows), 0, rows)

    moved, back = jax.vjp(lambda x: moe._to_window(x, *window), x)
    np.testing.assert_array_equal(_f32(moved),
                                  _f32(x)[np.asarray(pairs) // k])
    (gx,) = back(g_rows)
    np.testing.assert_array_equal(
        _f32(gx), _f32(moe._from_window(g_rows, *window)))
    _one_rounding(gx, _scatter_add(g_rows, inverse, run))

    (g_back,) = jax.vjp(lambda r: moe._from_window(r, *window), g_rows)[1](x)
    np.testing.assert_array_equal(_f32(g_back)[own], _f32(moved)[own])
    (plain,) = jax.vjp(lambda r: moe._from_window.fun(r, *window),
                       g_rows)[1](x)
    np.testing.assert_array_equal(_f32(g_back)[own], _f32(plain)[own])


# --------------------------------- what the v5e's compiler makes of the sum
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape", [(16384, 6, 2688), (8192, 10, 3072)],
                         ids=["nemotron-ep16-s8192", "laguna-ep32-s8192"])
def test_the_layout_pads_no_slot_axis_on_the_v5e(one_chip, shape):
    """At the two cells' shapes, compiled for a described v5e (nothing
    runs): token-major, the gathered rows were reshaped to bf16[N, k, D] with
    k on the tiled second-minor axis, a copy that pads 6 to 8 and 10 to 16
    (temporaries 1.15 and 1.22 GiB); slot-major the reshape is a bitcast and
    the temporaries are the gathered rows themselves."""
    tokens, k, width = shape

    def like(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, at = moe.window_rows(tokens * k), like((), jnp.int32)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(moe._from_window).lower(
            like((R, width), jnp.bfloat16), like((R,), jnp.int32),
            like((tokens, k), jnp.int32), (at, at, at)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    entry = compiled.as_text().split("\nENTRY ")[1].split("\n}")[0]
    padded = re.compile(rf"= bf16\[{tokens},{k},{width}\]")
    assert not [line for line in entry.splitlines() if padded.search(line)]
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1.1 * tokens * k * width * 2


@pytest.mark.parametrize("shape", [
    (16384, 4, 2048), (8192, 8, 4096), (16384, 8, 2048), (16384, 6, 2688),
    (8192, 10, 3072), (8192, 8, 2048)],
    ids=["lfm2-ep4-s8192", "solar-open2-ep40-tp8", "sdar-ep8-s8192",
         "nemotron-ep16-s8192", "laguna-ep32-s8192", "joyai-ep16-s8192"])
def test_on_the_v5e_the_return_is_the_kernel_and_gathers_no_slot(
        monkeypatch, one_chip, shape):
    """At the six cells' shapes, compiled for a described v5e whose backend
    says so of itself (nothing runs): one Mosaic call, the window's rows in
    token order the one large temporary, and no array of k x N rows."""
    tokens, k, width = shape
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def like(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, at = moe.window_rows(tokens * k), like((), jnp.int32)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(lambda *window: moe._from_window(*window)).lower(
            like((R, width), jnp.bfloat16), like((R,), jnp.int32),
            like((tokens, k), jnp.int32), (at, at, at)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "moe_window_return" in text
    assert not re.search(
        rf"bf16\[({tokens * k},{width}|{k},{tokens},{width})\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1.5 * R * width * 2
