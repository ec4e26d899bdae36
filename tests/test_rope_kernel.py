"""``ops/rope_kernel.py``: the rotary pass as a Mosaic kernel that rolls the
lanes, in interpret mode against the float32 sliced formula and against the
product of ``models/layers.py`` it stands in for; the rule that picks between
them; the call under a mesh; the step lowered for the TPU."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import layers, llama
from ray_tpu.ops import rope_kernel
from ray_tpu.parallel import MeshSpec, make_mesh
from ray_tpu.util import first_call

THETA = 10000.0
YARN = layers.Yarn(factor=32.0, original=4096)


def sliced(x, theta, rotary=None, interleave=False, first=False,
           inv_freq=None, scale=1.0, copies=1):
    """``tests/test_llama.py:_sliced_rope``, the formula as published, with
    ``layers.rope``'s other arguments: float32 parts, sliced, rotated, put
    back together, rounded once."""
    S, hd = x.shape[1], x.shape[3]
    rot = hd if rotary is None else rotary
    half = rot // 2
    if inv_freq is None:
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    else:
        freqs = jnp.asarray(np.asarray(inv_freq, np.float32))
    positions = jnp.arange(S) % (S // copies)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :] * scale
    sin = jnp.sin(angles)[None, :, None, :] * scale
    x32 = x.astype(jnp.float32)
    part = x32[..., :rot] if first or rot == hd else x32[..., hd - rot:]
    if interleave:
        x1, x2 = part[..., 0::2], part[..., 1::2]
    else:
        x1, x2 = part[..., :half], part[..., half:]
    r1, r2 = x1 * cos - x2 * sin, x2 * cos + x1 * sin
    if interleave:
        turned = jnp.stack([r1, r2], axis=-1).reshape(part.shape)
    else:
        turned = jnp.concatenate([r1, r2], axis=-1)
    if rot != hd:
        rest = x32[..., rot:] if first else x32[..., :hd - rot]
        turned = jnp.concatenate([turned, rest] if first else [rest, turned],
                                 axis=-1)
    return turned.astype(x.dtype)


def last_place(want):
    """The size of a bf16's last place at each of ``want``'s values, and of
    2^-10's under it: a result that small is what two float32 products of
    values near one nearly cancel to, and shows their own roundings."""
    return np.ldexp(np.float32(1),
                    np.maximum(np.frexp(np.abs(want))[1], -9) - 8)


def _hold(got, want, dtype, what):
    """float32: to its rounding (the kernel and XLA may round a
    multiply-add once or twice); bf16: within one last place, and nearly
    everywhere bit for bit."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5, err_msg=what)
        return
    assert (np.abs(got - want) <= last_place(want)).all(), what
    assert (got != want).mean() < 0.01, what


def _both(rope, xs, weights):
    """-> (values, gradients) of ``rope`` over the tuple ``xs``, jitted, and
    traced anew at every call (jit keeps its traces by the function: one
    traced before ``on_chip`` was replaced would answer for the product)."""
    def weighted(xs):
        return sum(jnp.sum(y.astype(jnp.float32) * w)
                   for y, w in zip(rope(xs), weights))
    return jax.jit(lambda xs: rope(xs))(xs), jax.jit(jax.grad(weighted))(xs)


def _arrays(shapes, dtype, seed=0):
    keys = jax.random.split(jax.random.key(seed), 2 * len(shapes))
    xs = tuple(jax.random.normal(k, s).astype(dtype)
               for k, s in zip(keys, shapes))
    return xs, tuple(jax.random.normal(k, s)
                     for k, s in zip(keys[len(shapes):], shapes))


HOW = {
    "whole": {},
    "first64": dict(rotary=64, first=True),
    "last64": dict(rotary=64),
    "yarn": dict(rotary=64, first=True, scale=YARN.scale,
                 inv_freq=YARN.inv_freq(64, THETA)),
    "yarn-whole": dict(scale=1.25, inv_freq=YARN.inv_freq(128, THETA)),
    "copies2": dict(copies=2),
}


@pytest.mark.parametrize("heads", [8, 72])
@pytest.mark.parametrize("how", sorted(HOW))
@pytest.mark.parametrize("hd", [128, 256])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_kernel_equals_the_sliced_formula_and_the_product(
        monkeypatch, dtype, hd, how, heads):
    """Values and ``jax.grad`` of the kernel (the interpreter's run of it)
    against the sliced float32 formula with autodiff through it, and against
    the product path, which the same call takes off the chip."""
    how = HOW[how]
    if hd == 256 and how.get("inv_freq") and "rotary" not in how:
        how = dict(how, inv_freq=YARN.inv_freq(256, THETA))
    xs, weights = _arrays([(2, 64, heads, hd)], dtype, seed=heads + hd)

    def program(xs):
        return layers.rope(xs, THETA, **how)

    product = _both(program, xs, weights)
    monkeypatch.setattr(rope_kernel, "on_chip", lambda: True)
    with first_call.noting() as notes:
        kernel = _both(program, xs, weights)
    assert notes["rope_kernel"] is True and notes["rope_calls"] >= 1
    formula = _both(lambda xs: tuple(sliced(x, THETA, **how) for x in xs),
                    xs, weights)
    for got, want, what in ((kernel, formula, "the formula"),
                            (kernel, product, "the product")):
        for g, w, part in zip(got, want, ("values", "gradient")):
            assert g[0].dtype == dtype and g[0].shape == xs[0].shape
            _hold(g[0], w[0], dtype, f"{part} against {what}")


def test_the_sliced_formula_is_test_llamas_at_the_defaults():
    from test_llama import _sliced_rope

    x = jax.random.normal(jax.random.key(5), (2, 48, 3, 128)
                          ).astype(jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(sliced(x, THETA), np.float32),
        np.asarray(_sliced_rope(x, THETA), np.float32))


@pytest.mark.parametrize("how", ["whole", "yarn", "copies2"])
def test_one_call_for_q_and_k_equals_two_calls(monkeypatch, how):
    """q and k of a layer share the tables: one ``pallas_call`` with two
    inputs and two outputs, bit for bit what a call each gives, values and
    gradients; and q may come as the projection writes it, (B, S, H x hd)."""
    monkeypatch.setattr(rope_kernel, "on_chip", lambda: True)
    how = HOW[how]
    (q, k), weights = _arrays([(2, 128, 12, 128), (2, 128, 4, 128)],
                              jnp.bfloat16)

    def together(xs):
        return layers.rope(xs, THETA, **how)

    def apart(xs):
        return tuple(layers.rope(x, THETA, **how) for x in xs)

    def flat(xs):
        return layers.rope(tuple(x.reshape(2, 128, -1) for x in xs), THETA,
                           hd=128, **how)

    jaxpr = str(jax.make_jaxpr(together)((q, k)))
    assert jaxpr.count("pallas_call") == 1
    assert str(jax.make_jaxpr(apart)((q, k))).count("pallas_call") == 2
    one = _both(together, (q, k), weights)
    for other in (apart, flat):
        for got, want in zip(jax.tree.leaves(one),
                             jax.tree.leaves(_both(other, (q, k), weights))):
            assert got.shape == want.shape
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("how", ["whole", "yarn"])
def test_the_kernel_scales_q_on_its_way_out(monkeypatch, how, dtype):
    """``scales``: q leaves multiplied by the attention's scale (a value of
    q's dtype, the product in float32 before the one rounding, as XLA fuses
    ``rope(q) * scale``), and its cotangent is scaled so before it is turned
    back; k is left alone.  The product form is bit for bit the multiply
    after it; the kernel within a last place of both."""
    how, scale = HOW[how], 128 ** -0.5
    xs, weights = _arrays([(2, 64, 12, 128), (2, 64, 4, 128)], dtype, seed=7)
    in_dtype = float(np.asarray(scale, dtype))

    def program(xs):
        return layers.rope(xs, THETA, scales=(scale, None), **how)

    def after(xs):
        q, k = layers.rope(xs, THETA, **how)
        return q * scale, k

    def formula(xs):  # float32 to the end, one rounding
        q, k = xs
        return ((sliced(q.astype(jnp.float32), THETA, **how) * in_dtype
                 ).astype(dtype), sliced(k, THETA, **how))

    for got, want in zip(jax.tree.leaves(_both(program, xs, weights)),
                         jax.tree.leaves(_both(after, xs, weights))):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    monkeypatch.setattr(rope_kernel, "on_chip", lambda: True)
    kernel = _both(program, xs, weights)
    assert "pallas_call" in str(jax.make_jaxpr(program)(xs))
    for got, want in zip(jax.tree.leaves(kernel),
                         jax.tree.leaves(_both(formula, xs, weights))):
        _hold(got, want, dtype, "the kernel's scale against the formula's")
    np.testing.assert_array_equal(  # k: no factor
        np.asarray(kernel[0][1], np.float32),
        np.asarray(jax.jit(lambda xs: layers.rope(xs, THETA, **how))(xs)[1],
                   np.float32))


def test_the_attention_that_scales_q_is_told_by_its_name(monkeypatch):
    """``scales_q``: the splash call multiplies q by the scale before its
    kernel; the einsum and the ring scale the scores.  A layer hands the
    scale to the rotary pass only there, so the tiny presets trace as they
    did (their lowered texts are pinned)."""
    from ray_tpu.ops import attention

    assert [attention.scales_q(impl) for impl in
            ("splash", "auto", "xla", "ring", "ulysses")] == \
        [True, False, False, False, False]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention.scales_q("auto") and not attention.scales_q("xla")


def test_under_fsdp4_every_chip_rotates_its_own_rows(monkeypatch):
    """Under a four-device mesh the kernel's call sits in
    ``placement.place``'s ``shard_map``, rows over `fsdp`: values and
    gradients equal the unplaced call's, and every chip's call saw one row.
    (``rope_kernel.path`` keeps the product under such a mesh for now, so the
    test hands the arrays to the kernel's form itself.)"""
    monkeypatch.setattr(rope_kernel, "on_chip", lambda: True)
    xs, weights = _arrays([(4, 64, 8, 128), (4, 64, 2, 128)], jnp.bfloat16)

    def program(xs):
        return layers._rope_by_kernel(xs, THETA, 64, True, None, 1.0, 1, None)

    want = _both(program, xs, weights)
    with jax.set_mesh(make_mesh(MeshSpec(fsdp=4), jax.devices()[:4])):
        jaxpr = str(jax.make_jaxpr(program)(xs))
        got = _both(program, xs, weights)
    assert "shard_map" in jaxpr and "pallas_call" in jaxpr
    # the kernel's blocks inside the shard_map are one row's
    assert re.search(r"bf16\[1,64,1024\]", jaxpr)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def _abstract_mesh(**axes):
    return jax.make_mesh(tuple(axes.values()), tuple(axes)).abstract_mesh


#: shapes, interleave, copies, the mesh's axes, on the chip -> the path
PATHS = {
    "128-on-the-chip": ([(1, 512, 32, 128), (1, 512, 8, 128)], False, 1, {},
                        True, "kernel"),
    "256-on-the-chip": ([(1, 512, 8, 256)], False, 1, {}, True, "kernel"),
    "two-copies": ([(1, 1024, 32, 128)], False, 2, {}, True, "kernel"),
    "one-device-mesh": ([(1, 512, 32, 128), (1, 512, 8, 128)], False, 1,
                        {"data": 1}, True, "kernel"),
    # a mesh of more than one device: the product, until a trace says why
    # `fsdp=4` lost 2.6 % with the kernel (PERF.md, PR 53)
    "fsdp4": ([(4, 512, 32, 128), (4, 512, 8, 128)], False, 1,
              {"fsdp": 4}, True, "product"),
    "data2.tensor2": ([(2, 512, 32, 128), (2, 512, 8, 128)], False, 1,
                      {"data": 2, "tensor": 2}, True, "product"),
    "128-off-the-chip": ([(1, 512, 32, 128)], False, 1, {}, False, "product"),
    "192": ([(1, 512, 32, 192)], True, 1, {}, True, "product"),
    "64": ([(1, 512, 1, 64)], True, 1, {}, True, "product"),
    "16": ([(2, 128, 4, 16)], False, 1, {}, True, "product"),
    "interleaved-128": ([(1, 512, 32, 128)], True, 1, {}, True, "product"),
    "seq4": ([(4, 512, 32, 128)], False, 1, {"seq": 4}, True, "product"),
    "rows-fsdp-does-not-divide": ([(2, 512, 32, 128)], False, 1,
                                  {"fsdp": 4}, True, "product"),
    "kv-heads-tensor-does-not-divide": (
        [(1, 512, 32, 128), (1, 512, 2, 128)], False, 1, {"tensor": 4}, True,
        "product"),
    "positions-no-block-divides": ([(1, 72, 8, 128)], False, 1, {}, True,
                                   "product"),
}


@pytest.mark.parametrize("case", sorted(PATHS))
def test_the_path_is_read_from_the_call(monkeypatch, case):
    shapes, interleave, copies, axes, on_chip, want = PATHS[case]
    if on_chip:  # what the described TPU's backend says of itself
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = _abstract_mesh(**axes) if axes \
        else jax.sharding.get_abstract_mesh()
    assert rope_kernel.path(shapes, interleave, copies, mesh) == want


@pytest.mark.parametrize("heads, positions, itemsize, want", [
    ((72, 8), 8192, 2, (512, 8)), ((48, 8), 8192, 2, (512, 4)),
    ((32, 8), 8192, 2, (512, 4)), ((32, 8), 1024, 2, (512, 4)),
    ((16, 16), 4096, 2, (512, 4)), ((32, 4), 8192, 2, (512, 4)),
    ((72,), 8192, 2, (512, 9)), ((8,), 8192, 4, (512, 1)),
    ((72, 8), 8192, 4, (512, 8)), ((127,), 8192, 2, (64, 1)),
    ((8,), 96, 2, (32, 1)), ((8,), 72, 2, None), ((509,), 8192, 2, None)])
def test_the_blocks_fit_the_row_the_heads_and_the_scoped_vmem(
        heads, positions, itemsize, want):
    """The most groups that leave a step eight heads, then the most positions
    up to 512 that divide the row and whose step, in and out in two buffers
    each, leaves a quarter of the 16 MiB free."""
    assert rope_kernel.blocks(positions, heads, 128, itemsize) == want
    if want:
        block, groups = want
        assert all(H % groups == 0 for H in heads)
        assert 4 * block * sum(heads) // groups * 128 * itemsize <= 12 << 20


def test_a_two_layer_step_lowers_for_the_tpu_with_the_kernel(monkeypatch):
    """Lowering for the TPU needs no TPU.  A two-layer GQA step at head 128
    holds the rotary kernel's calls (a scanned layer's forward, its recomputed
    forward and its backward, q and k in one call each) and no product with a
    permutation; the first-call notes say so."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, S, H, KV, hd = 2, 512, 8, 2, 128
    config = llama.LlamaConfig(vocab_size=1024, n_layer=2, n_head=H,
                               n_kv_head=KV, d_model=H * hd, d_ff=1536,
                               seq_len=S)
    optimizer = llama.make_optimizer()
    params = jax.eval_shape(lambda k: llama.init_params(config, k),
                            jax.random.key(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    batch = jax.ShapeDtypeStruct((B, S), jnp.int32)
    with first_call.noting() as notes:
        text = jax.jit(llama.make_train_step(config, optimizer)).trace(
            params, opt_state, batch, batch).lower(
                lowering_platforms=("tpu",)).as_text()
    assert notes["rope_kernel"] is True and notes["rope_calls"] == 1
    calls = [line for line in text.splitlines() if "@tpu_custom_call" in line]
    forward = [c for c in calls if "rope_forward" in c]
    backward = [c for c in calls if "rope_backward" in c]
    assert 1 <= len(forward) <= 2 and len(backward) == 1
    q, kv = f"tensor<{B}x{H}x{S}x{hd}xbf16>", f"tensor<{B}x{KV}x{S}x{hd}xbf16>"
    for call in forward:  # q and k out head-major, as the splash call reads
        out = call[call.rindex(") -> ("):]
        assert (out.count(q), out.count(kv)) == (1, 1)
    # no (hd x hd) permutation multiplies an activation anywhere
    assert not re.search(rf"dot_general.*tensor<{hd}x{hd}xbf16>", text)
    # and the kernel scales q itself: no multiply makes or reads a q
    assert not [line for line in text.splitlines()
                if "stablehlo.multiply" in line and (q in line or re.search(
                    rf"tensor<{B}x{S}x{H}x{hd}xbf16>", line))]
