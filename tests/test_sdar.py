"""SDAR-30B-A3B through ``models/llama.py``: block-diffusion training
(``models/block_diffusion.py``, ``ops.attention.block_diffusion_attention``),
``head_dim`` apart from ``d_model / n_head``, per-head QK-norm, and
``models/moe.py``'s expert layer holding a share of the experts.

The plain reference is ``benchmarks/reference/sdar.py``, the one copy (float32,
dense mask, every held expert applied to every position).  Everything runs on
the CPU with seeded random weights at tiny sizes, attention on the einsum path
but for the cases that run the splash kernel in interpret mode; the
grouped matmul has no other path than its kernel in interpret mode.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cost_sdar, spec, traffic as traffic_lib
from benchmarks.reference import sdar as reference
from ray_tpu.models import block_diffusion, llama, moe
from ray_tpu.ops import attention
from ray_tpu.ops.grouped_matmul import grouped_matmul
from tests import families
from tests.families import rel_err
from tests.test_olmoe import _equations, _loops

# ------------------------------------------------ (a) against the reference
# (loss and gradients, float32 and bfloat16: ``tests/test_families.py``, the
# row ``sdar``)
def test_an_untrained_model_reads_ln_v():
    """Whatever the weights of the block-diffusion loss."""
    config, _ = families.built("sdar", "float32")
    ref_loss = families.compared("sdar", "float32")["ref_loss"]
    assert abs(float(ref_loss) - np.log(config["vocab_size"])) < 0.1


def test_query_blocks_do_not_change_the_reference():
    config, family = families.family("sdar", "float32")
    params = jax.jit(family.init_fn)(jax.random.key(1))
    rows = np.random.default_rng(1).integers(0, 511, (1, 128)).astype(np.int32)
    whole = family.reference_loss(params, rows, None, 256)
    blocks = family.reference_loss(params, rows, None, 64)
    assert float(whole) == pytest.approx(float(blocks), rel=1e-6)


# ------------------------------------------------------------ (b) the mask
def _brute_force(S, Bk):
    """The rule, one pair at a time."""
    want = np.zeros((2 * S, 2 * S), bool)
    for i in range(2 * S):
        for j in range(2 * S):
            bi, bj = (i % S) // Bk, (j % S) // Bk
            if i < S and j < S:
                want[i, j] = bi == bj
            elif i < S <= j:
                want[i, j] = bj < bi
            elif i >= S and j >= S:
                want[i, j] = bj <= bi
    return want


@pytest.mark.parametrize("S,Bk", [(8, 2), (12, 4), (16, 1), (16, 16),
                                  (24, 3)])
def test_allowed_is_the_rule(S, Bk):
    at = np.arange(2 * S)
    got = block_diffusion.allowed(at[:, None], at[None, :], S, Bk)
    want = _brute_force(S, Bk)
    assert np.array_equal(got, want)
    # the same on traced integers, as the kernel calls it
    traced = jax.jit(lambda i, j: block_diffusion.allowed(i, j, S, Bk))(
        jnp.asarray(at)[:, None], jnp.asarray(at)[None, :])
    assert np.array_equal(np.asarray(traced), want)
    # the reference's own spelling, and the area the cost functions count
    assert np.array_equal(np.asarray(reference.may_read(
        jnp.asarray(at), jnp.asarray(at), S, Bk)), want)
    assert want.sum() == cost_sdar.mask_area(S, Bk) == S * S + S * Bk
    assert want.any(axis=1).all()  # no query without a key


@pytest.mark.parametrize("S,Bk", [(8, 2), (48, 12), (16, 1), (128, 4),
                                  (64, 64)])
@pytest.mark.parametrize("block", [8, 16, 512])
def test_the_stored_mask_is_the_rule(S, Bk, block):
    """What the splash library reads of the mask, a kernel block at a time
    (shorter where the row is), is the rule on every pair of the 2S
    positions.  A block length that is not a power of two among them."""
    at = np.arange(2 * S)
    want = block_diffusion.allowed(at[:, None], at[None, :], S, Bk)
    block = min(block, 2 * S)
    mask = attention._block_diffusion_mask()(S, Bk)
    assert mask.shape == want.shape
    assert np.array_equal(mask[:, :], want)
    for i in range(0, 2 * S, block):
        for j in range(0, 2 * S, block):
            chunk = mask[slice(i, i + block), slice(j, j + block)]
            assert chunk.dtype == np.bool_
            assert np.array_equal(chunk, want[i:i + block, j:j + block])
    # by value: the library's cache of processed masks finds it again
    again = attention._block_diffusion_mask()(S, Bk)
    assert mask == again and hash(mask) == hash(again)
    assert mask != attention._block_diffusion_mask()(S, 2 * Bk)


def test_whole_tiles_pay_nothing_for_the_mask():
    """The cell's row (2S = 16384, block length 4, 512-blocks): of the 288
    blocks with work a head 48 are cut and read one of 3 stored tiles, the
    other 240 are told apart as whole; the forward walks 544 grid steps and
    the fused backward, whose grid the library does not shrink, 1024."""
    S, Bk, H = 8192, 4, 2
    kernel, counts = attention._splash_kernel(
        2 * S, H, 128, True, Bk, attention.SplashBlocks.square(512))
    assert counts == {"attn_calls": 1, "attn_blocks": 288,
                      "attn_blocks_cut": 48, "attn_grid_steps_fwd": 544,
                      "attn_grid_steps_bwd": 1024, "attn_block_q": 512,
                      "attn_block_kv": 512, "attn_block_q_bwd": 512,
                      "attn_block_kv_bwd": 512, "attn_dq_partials": 32}
    assert kernel.kwargs["mask_function"] is None
    for info in (kernel.fwd_mask_info, kernel.dkv_mask_info):
        assert info.q_sequence is None  # no mask computed in the kernel
        block_mask = np.asarray(info.block_mask)
        assert (block_mask == 1).sum() == 48 and (block_mask == 2).sum() == 240
        # every cut block points at a tile the kernel holds
        assert info.partial_mask_blocks.shape == (3, 512, 512)
        assert np.asarray(info.mask_next)[block_mask == 1].max() < 3
    tiles = np.asarray(kernel.fwd_mask_info.partial_mask_blocks)
    # the backward's tiles are the forward's, kv-major
    assert np.array_equal(
        np.asarray(kernel.dkv_mask_info.partial_mask_blocks),
        tiles.swapaxes(-1, -2))
    # the three kinds of diagonal tile: the noised copy's own blocks, the
    # clean copy's earlier blocks, the clean copy's own and earlier blocks
    at = np.arange(512)
    mine, theirs = at[:, None] // Bk, at[None, :] // Bk
    assert {t.tobytes() for t in tiles} == {
        (mine == theirs).tobytes(), (theirs < mine).tobytes(),
        (theirs <= mine).tobytes()}


def test_a_causal_row_is_the_call_it_was():
    """``block_length`` 0: the library's ``CausalMask``, the mask computed
    in the kernel on every block with work."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    S, H = 2048, 2
    kernel, counts = attention._splash_kernel(
        S, H, 128, True, blocks=attention.SplashBlocks.square(512))
    assert counts == {"attn_calls": 1, "attn_blocks": 10,
                      "attn_blocks_cut": 10, "attn_grid_steps_fwd": 16,
                      "attn_grid_steps_bwd": 16, "attn_block_q": 512,
                      "attn_block_kv": 512, "attn_block_q_bwd": 512,
                      "attn_block_kv_bwd": 512, "attn_dq_partials": 4}
    assert kernel.kwargs["mask_function"] is not None
    want = sk.make_splash_mha(
        sm.MultiHeadMask([sm.CausalMask((S, S))] * H), head_shards=1,
        q_seq_shards=1, block_sizes=kernel.kwargs["block_sizes"],
        interpret=True, residual_checkpoint_name=attention.SPLASH_RESIDUALS)
    for got, ref in ((kernel.fwd_mask_info, want.fwd_mask_info),
                     (kernel.dkv_mask_info, want.dkv_mask_info)):
        assert got.partial_mask_blocks is None and got.mask_next is None
        for a, b in zip(got, ref):
            assert (a is None) == (b is None)
            assert a is None or np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("S,Bk", [(128, 4), (384, 12)],
                         ids=["two-blocks-a-side", "block-length-12"])
def test_splash_kernel_agrees_with_the_einsum(monkeypatch, S, Bk):
    """The interpret-mode runs of the splash kernel under the block mask:
    forward and the fused backward, GQA, 128-blocks so that empty, whole and
    cut-through blocks all occur (the cut ones read stored tiles), and a
    block length that is not a power of two."""
    B, H, KV, hd = 1, 4, 2, 32
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, 2 * S, H, hd))
    k, v = (jax.random.normal(key, (B, 2 * S, KV, hd)) for key in ks[1:3])
    do = jax.random.normal(ks[3], q.shape)
    monkeypatch.setattr(attention, "_splash_kernel", functools.partial(
        attention._splash_kernel,
        blocks=attention.SplashBlocks.square(128)))

    def run(impl):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(attention.block_diffusion_attention(
                q, k, v, Bk, impl) * do), argnums=(0, 1, 2)))(q, k, v)

    (a, ga), (b, gb) = run("xla"), run("splash")
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    for x, y in zip(ga, gb):
        assert rel_err(y, x) < 1e-5


@pytest.mark.parametrize("impl", ["ring", "ulysses", "flash"])
def test_block_mask_has_no_other_path(impl):
    q = jnp.zeros((1, 16, 2, 8))
    with pytest.raises(ValueError):
        attention.block_diffusion_attention(q, q, q, 4, impl)
    with pytest.raises(ValueError, match="must divide"):
        attention.block_diffusion_attention(q, q, q, 3, "xla")


# ----------------------------------------------------------- (c) the share
def _layer(n_experts=8, k=2, tokens=48, d=32, f=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    h = jax.random.normal(ks[0], (1, tokens, d))
    blk = {"router": jax.random.normal(ks[1], (d, n_experts)) * 2.0,
           "w_gate": jax.random.normal(ks[2], (n_experts, d, f)) * 0.2,
           "w_up": jax.random.normal(ks[3], (n_experts, d, f)) * 0.2,
           "w_down": jax.random.normal(ks[4], (n_experts, f, d)) * 0.2}
    return h, blk


def _share(blk, first, stop):
    return {"router": blk["router"],
            **{n: blk[n][first:stop] for n in ("w_gate", "w_up", "w_down")}}


@pytest.mark.parametrize("per_chip", [1, 4, 8], ids=lambda n: f"{8 // n}x{n}")
def test_the_shares_add_up_to_the_whole_layer(per_chip):
    """What every chip of an expert-parallel job computes for the same
    tokens adds up to the uncut reference's whole layer; the router's losses
    are the same on every chip."""
    h, blk = _layer()
    cfg = {"num_experts_published": 8, "num_experts_per_tok": 2,
           "norm_topk_prob": True, "experts_held": [0, 8]}
    with jax.default_matmul_precision("highest"):
        whole, balance = reference.moe(h[0], blk, cfg)
    total, losses = 0.0, []
    for first in range(0, 8, per_chip):
        y, (lb, _), _ = jax.jit(functools.partial(
            moe.moe_mlp, experts_per_token=2, norm_topk_prob=True,
            dtype=jnp.float32, first_held=first))(
            h, _share(blk, first, first + per_chip))
        total = total + y[0]
        losses.append(float(lb))
        # the reference's share is the same part
        with jax.default_matmul_precision("highest"):
            part, _ = reference.moe(h[0], _share(blk, first, first + per_chip),
                                    dict(cfg, experts_held=[first,
                                                            first + per_chip]))
        assert rel_err(y[0], part) < 1e-5
    assert rel_err(total, whole) < 1e-5
    assert losses == pytest.approx([float(balance)] * len(losses), rel=1e-5)


def test_an_absent_experts_rows_cost_no_product():
    """Rows of a group ``rhs`` does not hold come out zero and carry no
    gradient; the held groups' rows are the plain products."""
    lhs = jax.random.normal(jax.random.key(0), (32, 16))
    rhs = jax.random.normal(jax.random.key(1), (4, 16, 8))
    sizes = jnp.asarray([5, 11, 0, 16], jnp.int32)

    def held(lhs, rhs):
        return grouped_matmul(lhs, rhs[1:3], sizes, 1)

    out, vjp = jax.vjp(held, lhs, rhs)
    assert np.array_equal(np.asarray(out[:5]), np.zeros((5, 8)))
    assert np.array_equal(np.asarray(out[16:]), np.zeros((16, 8)))
    assert rel_err(out[5:16], lhs[5:16] @ rhs[1]) < 1e-5
    dlhs, drhs = vjp(jnp.ones_like(out))
    assert not np.asarray(dlhs[:5]).any() and not np.asarray(dlhs[16:]).any()
    assert not np.asarray(drhs[0]).any() and not np.asarray(drhs[3]).any()
    assert not np.asarray(drhs[2]).any()  # held, and empty
    assert rel_err(drhs[1], lhs[5:16].T @ jnp.ones((11, 8))) < 1e-5


# ------------------------------------------- (c2) the windows on the held run
#: 32 tokens x 2 experts a token: 64 pairs, a window of 8 rows
_N, _K, _E, _H = 32, 2, 8, 2


def _pairs_with_a_run(run, first_held, absent):
    """(N, k) expert ids of which exactly ``run`` pairs name one of the two
    held experts (slot 0 the first, slot 1 the second, tokens in turn); the
    other pairs go to the experts in ``absent``."""
    experts = np.empty((_N, _K), np.int32)
    for slot in range(_K):
        experts[:, slot] = [absent[(t + slot * 3) % len(absent)]
                            for t in range(_N)]
    for j in range(run):
        experts[j % _N, j // _N] = first_held + j // _N
    assert np.isin(experts, [first_held, first_held + 1]).sum() == run
    return jnp.asarray(experts)


def _window_layer(seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    d, f = 32, 16
    return (jax.random.normal(ks[0], (_N, d)),
            jax.nn.softmax(jax.random.normal(ks[1], (_N, _K))),
            jax.random.normal(ks[2], (_H, d, f)) * 0.2,
            jax.random.normal(ks[3], (_H, d, f)) * 0.2,
            jax.random.normal(ks[4], (_H, f, d)) * 0.2)


def _move_every_pair(first_held, experts, x, weights, w_gate, w_up, w_down):
    """The share's layer as it was before it had windows: every pair a row,
    the kernels told which groups ``rhs`` holds."""
    order, inverse, sizes = moe.sort_pairs(experts, _E)
    rows = moe._to_expert_order(x, order, inverse)
    w_rows = moe._weights_to_expert_order(weights, order, inverse)
    gate = grouped_matmul(rows, w_gate, sizes, first_held)
    up = grouped_matmul(rows, w_up, sizes, first_held)
    act = jax.nn.silu(gate) * up * w_rows[:, None]
    return moe._combine(grouped_matmul(act, w_down, sizes, first_held),
                        order, inverse)


@functools.lru_cache(maxsize=None)
def _window_programs(first_held):
    """(the windows, the move of every pair, the gradients of each), jitted
    over (expert ids, the five floats): how many windows are walked is the
    data's, so one compiled program serves every length of run."""
    def windowed(experts, *floats):
        return moe.expert_mlp(floats[0], floats[1], experts, *floats[2:],
                              _E, first_held)

    full = functools.partial(_move_every_pair, first_held)

    def gradients(layer):
        return jax.jit(jax.grad(
            lambda experts, dy, *floats: jnp.sum(layer(experts, *floats) * dy),
            argnums=range(2, 7)))

    return (jax.jit(windowed), jax.jit(full),
            gradients(lambda *a: windowed(*a)[0]), gradients(full))


@pytest.mark.parametrize("first_held,absent", [
    (0, (2, 5, 7)),          # the run begins the sorted order
    (3, (5, 6, 7)),          # ... and here too, behind no absent expert's pair
    (3, (0, 2, 6)),          # absent pairs on both sides of it
    (6, (0, 1, 2, 3)),       # the run ends the order: a window begins early
], ids=["first0", "first3-at-start", "first3-inside", "first6-at-end"])
@pytest.mark.parametrize("run,moved", [
    (0, 0), (5, 8), (8, 8), (9, 16), (16, 16), (17, 24), (33, 40), (63, 64),
    (64, 64),
], ids=["empty", "inside-a-window", "exactly-a-window", "one-row-over",
        "two-windows", "a-group-cut-by-a-window", "past-half", "all-but-one",
        "every-pair-held"])
def test_the_windows_are_the_move_of_every_pair(first_held, absent, run,
                                                moved):
    """The layer that holds a share walks as many windows as its own count
    needs and gives what the move of every pair gives, to float32 rounding
    (the kernels walk a window in tiles of its size, so a product sums in
    another order; a token whose rows lie in two windows is summed in
    float32 across them): the output and the gradient of x, of the combine
    weights and of the three held matrices."""
    x, weights, w_gate, w_up, w_down = floats = _window_layer()
    experts = _pairs_with_a_run(run, first_held, absent)
    assert moe.window_rows(_N * _K) == 8
    windowed, full, windowed_grads, full_grads = _window_programs(first_held)

    def same(a, b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)

    y, held_rows, took = windowed(experts, *floats)
    assert int(held_rows.sum()) == run and int(took) == moved
    same(y, full(experts, *floats))
    # and against the layer written out densely, absent experts left out
    dense = jnp.zeros_like(x)
    for e in range(_H):
        w = jnp.sum(jnp.where(experts == first_held + e, weights, 0.0), 1)
        dense += w[:, None] * ((jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e]))
                               @ w_down[e])
    same(y, dense)
    dy = jax.random.normal(jax.random.key(9), y.shape)
    for a, b in zip(windowed_grads(experts, dy, *floats),
                    full_grads(experts, dy, *floats)):
        same(a, b)


def test_the_windows_under_remat_run_no_product_twice_but_gate_and_up():
    """``test_remat_recomputes_neither_the_down_projection_nor_the_combine``
    (tests/test_olmoe.py) for the windows.  The layer saves its arguments
    and nothing else: the forward is one loop of 3 kernels a window and
    keeps no window's rows; the backward is one loop whose pass runs gate
    and up again, then 3 dx and 3 dW.  Its ``jax.vjp`` also traces the
    forward's down-projection and combine, whose results nothing reads:
    what is counted is what dead-code elimination (the compiler's; jax's
    own, applied to the loop's body here) leaves."""
    from jax._src.interpreters import partial_eval as pe

    x, weights, w_gate, w_up, w_down = floats = _window_layer()
    experts = _pairs_with_a_run(13, 0, (2, 5, 7))
    layer = jax.checkpoint(lambda *a: moe.expert_mlp(
        a[0], a[1], experts, *a[2:], _E, 0)[0])
    traced = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(layer(*a) ** 2), argnums=range(5)))(*floats).jaxpr
    live, _ = pe.dce_jaxpr(traced, [True] * len(traced.outvars))
    # the rematted forward's loop feeds nothing and is gone
    forward, backward = _loops(live)
    # carries: the window's number and how many there are, then each
    # token's sum; the five gradients
    assert [len(e.outvars) for e in (forward, backward)] == [3, 7]
    d, rows = x.shape[1], moe.window_rows(_N * _K)

    def body(loop):
        jaxpr = loop.params["body_jaxpr"].jaxpr
        return pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))[0]

    def kernels(loop):
        return sum(e.primitive.name == "pallas_call"
                   for _, e in _equations(body(loop)))

    def row_gathers(loop):
        return sorted(e.outvars[0].aval.shape
                      for _, e in _equations(body(loop))
                      if e.primitive.name == "gather"
                      and e.outvars[0].aval.shape[-1] == d)

    assert kernels(forward) == 3 and kernels(backward) == 2 + 3 + 3
    # a window: the dispatch (R, D) and the combine, slot-major (k, N, D);
    # backwards: the dispatch again, the combine's transpose (R, D), the
    # dispatch's transpose (k, N, D), and no combine a second time
    assert row_gathers(forward) == sorted([(_K, _N, d), (rows, d)])
    assert row_gathers(backward) == sorted([(_K, _N, d), (rows, d),
                                            (rows, d)])
    assert not any(e.primitive.name.startswith("scatter")
                   and e.outvars[0].aval.shape[0] in (_N, _N * _K)
                   for _, e in _equations(live))


# ------------------------------------- (d) every expert held is today's layer
def test_holding_every_expert_is_the_layer_as_it_was():
    """With every expert held the layer traces to what it traced to before
    a share existed (the kernels' calls without a ``group_offset``), and its
    value and gradients are bit for bit those of that layer written out."""
    h, blk = _layer()
    k = 2

    def as_it_was(h, blk):
        tokens = h.reshape(-1, h.shape[-1])
        weights, experts, losses = moe.route(tokens, blk["router"], k, True)
        order, inverse, sizes = moe.sort_pairs(experts, 8)
        rows = moe._to_expert_order(tokens, order, inverse)
        w_rows = moe._weights_to_expert_order(weights, order, inverse)
        gate = grouped_matmul(rows, blk["w_gate"], sizes)
        up = grouped_matmul(rows, blk["w_up"], sizes)
        act = jax.nn.silu(gate) * up * w_rows[:, None]
        out = grouped_matmul(act, blk["w_down"], sizes)
        return moe._combine(out, order, inverse).reshape(h.shape), losses

    def now(h, blk):
        return moe.moe_mlp(h, blk, experts_per_token=k, norm_topk_prob=True,
                           dtype=jnp.float32)

    def value_and_grads(layer):
        return jax.jit(jax.value_and_grad(
            lambda blk: jnp.sum(layer(h, blk)[0] ** 2)))(blk)

    for a, b in zip(jax.tree.leaves(value_and_grads(now)),
                    jax.tree.leaves(value_and_grads(as_it_was))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the configuration's default is every expert, and says so
    config = llama.LlamaConfig.tiny_moe()
    assert config.experts_held is None and config.held == range(8)
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = jax.eval_shape(functools.partial(llama.init_params, config),
                            jax.random.key(0))
    short = dataclasses.replace(config, seq_len=16, attn_impl="xla")

    def traced(config):
        text = str(jax.make_jaxpr(functools.partial(
            llama.loss_fn, config=config))(params, tokens, tokens))
        return re.sub(r"0x[0-9a-f]+", "", text)  # objects' addresses

    assert traced(short) == traced(
        dataclasses.replace(short, experts_held=range(8)))
    # no windows where the run is every pair: no loop but the layers' scan
    # and the kernels' own, and one counter; a share has both
    def loops_and_counters(config):
        jaxpr = jax.make_jaxpr(functools.partial(
            llama.loss_and_counters, config=config))(params, tokens, tokens)
        counters = jax.eval_shape(functools.partial(
            llama.loss_and_counters, config=config), params, tokens,
            tokens)[1]
        return len(_loops(jaxpr.jaxpr)), \
            {name: c.shape for name, c in counters.items()}

    assert loops_and_counters(short) == (
        0, {"moe_rows": (short.n_layer, 1, 8)})
    share = dataclasses.replace(short, experts_held=range(2, 4))
    params = jax.eval_shape(functools.partial(llama.init_params, share),
                            jax.random.key(0))
    assert loops_and_counters(share) == (
        1, {"moe_rows": (share.n_layer, 1, 2),
            "moe_moved": (share.n_layer, 1)})


# ------------------------------------------------------------ (e) the noise
def test_noise_is_a_function_of_the_row_and_the_seed():
    rows = np.random.default_rng(0).integers(0, 500, (3, 64)).astype(np.int32)
    rows[2] = rows[0]
    draw = jax.jit(block_diffusion.masked_positions, static_argnums=(1, 2))
    masked, m = draw(rows, 7, 4)
    again, _ = draw(rows[::-1].copy(), 7, 4)
    assert np.array_equal(masked[0], masked[2])          # same row, same mask
    assert np.array_equal(masked, np.asarray(again)[::-1])  # whatever the batch
    assert not np.array_equal(masked[0], masked[1])
    assert not np.array_equal(masked, draw(rows, 8, 4)[0])  # another seed
    changed = rows.copy()
    changed[0, 5] += 1
    assert not np.array_equal(masked[0], draw(changed, 7, 4)[0][0])
    # each block lost exactly m, and the reference draws the same positions
    assert np.array_equal(np.asarray(masked).reshape(3, 16, 4).sum(-1), m)
    ref_masked, ref_m = reference.masked_positions(
        jnp.asarray(rows), {"block_length": 4, "noise_seed": 7})
    assert np.array_equal(masked, ref_masked) and np.array_equal(m, ref_m)


@pytest.mark.parametrize("Bk", [1, 4, 8])
def test_m_is_uniform_and_the_subset_too(Bk):
    rows = np.random.default_rng(Bk).integers(
        0, 500, (16, 512 * Bk)).astype(np.int32)
    masked, m = jax.jit(block_diffusion.masked_positions,
                        static_argnums=(1, 2))(rows, 34, Bk)
    m = np.asarray(m).reshape(-1)                        # 8192 blocks
    counts = np.bincount(m, minlength=Bk + 1)
    expect = m.size / (Bk + 1)
    assert len(counts) == Bk + 1
    assert np.all(np.abs(counts - expect) < 5 * np.sqrt(expect)), counts
    # every position of a block is masked equally often: m / Bk on average
    by_place = np.asarray(masked).reshape(-1, Bk).mean(axis=0)
    assert np.all(np.abs(by_place - 0.5) < 5 * 0.5 / np.sqrt(m.size))


def test_the_input_the_weights_and_the_mask_id():
    config, family = families.family("sdar")
    gen = traffic_lib.make(
        spec.load_json(spec.BENCH_DIR, "traffic", "packed-s8192-b1.json"),
        vocab_size=family.vocab_size, eod_id=family.eod_id, global_batch=2,
        seq_len=128, seed=3000000019)
    mask_id = config["mask_token_id"]
    for rows in (gen.batch(0)["tokens"], gen.check_rows(2)[:, :-1]):
        assert rows.max() < mask_id  # [MASK] never appears in clean data
        both, weights = jax.jit(
            block_diffusion.noise, static_argnums=(1, 2, 3))(
            rows, config["noise_seed"], 4, mask_id)
        noised, clean = np.asarray(both[:, :128]), np.asarray(both[:, 128:])
        assert np.array_equal(clean, rows)
        assert np.array_equal(noised == mask_id, np.asarray(weights) > 0)
        assert np.array_equal(noised[noised != mask_id],
                              rows[noised != mask_id])
        # weight 1/m in a block that lost m, over the blocks that lost any
        w = np.asarray(weights).reshape(2, 32, 4)
        lost = (w > 0).sum(-1)
        assert float(w.sum()) == pytest.approx(1.0, rel=1e-6)
        n_blocks = (lost > 0).sum()
        assert np.allclose(w[w > 0] * n_blocks,
                           (1.0 / np.maximum(lost, 1)[..., None] * (w > 0))[w > 0])
        assert w.max() * n_blocks <= 1.0 + 1e-6
        assert w[w > 0].min() * n_blocks >= 0.25 - 1e-6


# --------------------------------- (f) head_dim, per-head QK-norm, positions
def _by_hand(x, blk, config, S, Bk):
    """One layer's attention half in float64, a head and a query at a time:
    per-head RMSNorm over head_dim with one shared vector, rotate-half RoPE
    at the position within the copy, the mask by the rule."""
    x = np.asarray(x, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in blk.items()}
    P, D = x.shape
    H, KV, hd = config.n_head, config.n_kv_head, config.head_dim
    eps = config.rms_eps

    def rms(a, g):
        return a / np.sqrt(np.mean(a * a, axis=-1, keepdims=True) + eps) * g

    def rope(a, pos):
        half = hd // 2
        freq = config.rope_theta ** (-np.arange(half) / half)
        cos, sin = np.cos(pos * freq), np.sin(pos * freq)
        a1, a2 = a[:half], a[half:]
        return np.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin])

    h = rms(x, w["attn_norm"])
    q = (h @ w["wq"]).reshape(P, H, hd)
    k = (h @ w["wk"]).reshape(P, KV, hd)
    v = (h @ w["wv"]).reshape(P, KV, hd)
    out = np.zeros((P, H, hd))
    for head in range(H):
        kv = head // (H // KV)
        qs = np.stack([rope(rms(q[i, head], w["q_norm"]), i % S)
                       for i in range(P)])
        ks = np.stack([rope(rms(k[j, kv], w["k_norm"]), j % S)
                       for j in range(P)])
        for i in range(P):
            scores = qs[i] @ ks.T / np.sqrt(hd)
            scores = np.where(_MASKS[(S, Bk)][i], scores, -np.inf)
            p = np.exp(scores - scores.max())
            out[i, head] = (p / p.sum()) @ v[:, kv]
    return x + out.reshape(P, H * hd) @ w["wo"]


_MASKS = {(16, 0): np.tril(np.ones((16, 16), bool)),
          (8, 2): _brute_force(8, 2)}


@pytest.mark.parametrize("S,Bk", [(16, 0), (8, 2)],
                         ids=["causal", "block-diffusion"])
def test_head_dim_and_per_head_qk_norm_by_hand(S, Bk):
    """head_dim 128 where d_model / n_head is 64; QK-norm over each head."""
    config = llama.LlamaConfig(
        vocab_size=64, n_layer=1, n_head=4, n_kv_head=2, d_model=256,
        head_dim=128, d_ff=32, seq_len=S, rope_theta=1e6, rms_eps=1e-6,
        qk_norm="head", block_length=Bk, mask_token_id=63, attn_impl="xla",
        dtype=jnp.float32, logits_dtype=jnp.float32)
    assert config.head_dim == 128 != config.d_model // config.n_head
    params = llama.init_params(config, jax.random.key(0))
    shapes = jax.tree.map(lambda a: a.shape, params["blocks"])
    assert shapes["wq"] == (1, 256, 512) and shapes["wo"] == (1, 512, 256)
    assert shapes["wk"] == shapes["wv"] == (1, 256, 256)
    assert shapes["q_norm"] == shapes["k_norm"] == (1, 128)
    assert llama.num_params(config) == sum(
        a.size for a in jax.tree.leaves(params))
    ks = jax.random.split(jax.random.key(1), 3)
    blk = jax.tree.map(lambda a: a[0], params["blocks"])
    blk["q_norm"] = 1.0 + 0.3 * jax.random.normal(ks[0], (128,))
    blk["k_norm"] = 1.0 + 0.3 * jax.random.normal(ks[1], (128,))
    blk["wq"], blk["wk"] = blk["wq"] * 20, blk["wk"] * 20  # sharp scores
    blk["w_down"] = jnp.zeros_like(blk["w_down"])  # the MLP adds nothing
    P = 2 * S if Bk else S
    x = jax.random.normal(ks[2], (1, P, 256))
    with jax.default_matmul_precision("highest"):
        got, _ = llama._block(x, blk, config)
    assert rel_err(got[0], _by_hand(x[0], blk, config, S, Bk)) < 1e-4


@pytest.mark.parametrize("impl,covering", [
    ("xla", {}),
    # a row shorter than the kernel's 512-blocks: one block of 256 x 256, cut
    ("splash", {"attn_calls": 1, "attn_blocks": 1, "attn_blocks_cut": 1,
                "attn_grid_steps_fwd": 1, "attn_grid_steps_bwd": 1,
                "attn_block_q": 256, "attn_block_kv": 256,
                "attn_block_q_bwd": 256, "attn_block_kv_bwd": 256,
                "attn_dq_partials": 1}),
])
def test_first_call_says_what_the_model_is(impl, covering):
    """The ``train.first_call`` record carries the share and the block
    length, and where the splash kernel runs how its calls cover the mask;
    a causal dense model says so with zeros."""
    from ray_tpu.parallel.train_state import jit_train_step
    from ray_tpu.util import device_telemetry as dt

    dt.reset()
    config, family = families.family(
        "sdar", options={"attn_impl": impl})
    optimizer = family.make_optimizer()
    params = jax.jit(family.init_fn)(jax.random.key(0))
    opt_state = jax.jit(optimizer.init)(params)
    rows = jnp.zeros((1, 128), jnp.int32)
    step = jit_train_step(family.make_train_step(optimizer))
    _, _, loss = step(params, opt_state, rows, rows)
    assert np.isfinite(float(loss))
    first = dt.first_calls("train_step")[-1]
    assert {k: v for k, v in first.items()
            if k.startswith(("experts_", "block_", "attn_", "loss_"))} == {
        "experts_held": 2, "experts_total": 8, "block_length": 4,
        "attn_positions": 256, "loss_positions": 128, **covering}
    dt.reset()
