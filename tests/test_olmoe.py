"""OLMoE through ``models/llama.py``: QK-norm, ``models/moe.py``'s dropless
expert layer, ``ops/grouped_matmul.py``, the router losses.

The plain reference is ``benchmarks/reference/olmoe.py``, the one copy (float32,
every expert applied to every token, no sort, no grouped matmul).  Everything
runs on the CPU with seeded random weights at the tiny preset; the grouped
matmul is the Pallas kernel in interpret mode.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals  # not re-exported in 0.9

from ray_tpu.models import llama, moe
from ray_tpu.ops import remat
from ray_tpu.ops.attention import save_splash_residuals
from ray_tpu.ops.grouped_matmul import grouped_matmul, tile_for
from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh
from ray_tpu.parallel.mesh import pytree_sharding
from ray_tpu.util import first_call
from tests import families
from tests.families import rel_err


def _tiny(**kw):
    return dataclasses.replace(llama.LlamaConfig.tiny_moe(), attn_impl="xla",
                               **kw)


def _batch(config, rows=2, seed=1):
    tokens = jax.random.randint(jax.random.key(seed),
                                (rows, config.seq_len + 1), 0,
                                config.vocab_size)
    return tokens[:, :-1], tokens[:, 1:]


# ------------------------------------------------ (a) against the reference
# (loss and gradients, float32 and bfloat16: ``tests/test_families.py``, the
# row ``olmoe``)
def test_the_router_losses_are_in_the_loss():
    """Without the auxiliary and the z loss it is smaller."""
    got = families.compared("olmoe", "float32")
    config = families.float32("olmoe")
    bare = dataclasses.replace(config, router_aux_loss_coef=0.0,
                               router_z_loss_coef=0.0)
    assert float(llama.loss_fn(got["params"], got["tokens"], got["targets"],
                               bare)) < float(got["loss"]) - 0.009


# ------------------------------------- (b) the layer against a Python loop
def _layer_inputs(n_experts, k, n_tokens=48, d=32, f=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (n_tokens, d))
    router = jax.random.normal(ks[1], (d, n_experts))
    # skewed: expert 0 is out of reach for every token (no row), the last
    # expert is every token's first choice (most rows)
    logits_bias = jnp.zeros((n_experts,)).at[0].set(-1e4).at[-1].set(50.0)
    blk = {"router": router,
           "w_gate": jax.random.normal(ks[2], (n_experts, d, f)) * 0.2,
           "w_up": jax.random.normal(ks[3], (n_experts, d, f)) * 0.2,
           "w_down": jax.random.normal(ks[4], (n_experts, f, d)) * 0.2}
    return x, blk, logits_bias


def _with_bias(x, router, bias):
    """The layer's router has no bias; the tests force the skew through one
    more input feature, constant 1, whose row of the matrix is the bias."""
    return (jnp.concatenate([x, jnp.ones((x.shape[0], 1))], axis=1),
            jnp.concatenate([router, bias[None, :]], axis=0))


def _per_token_loop(x, weights, experts, blk, dy):
    """The layer and the gradients of ``sum(y * dy)``, one (token, slot) pair
    at a time in float64, derived by hand: no sort, no autodiff."""
    x, weights, dy = (np.asarray(a, np.float64) for a in (x, weights, dy))
    w_gate, w_up, w_down = (np.asarray(blk[name], np.float64)
                            for name in ("w_gate", "w_up", "w_down"))
    y = np.zeros_like(x)
    grads = {"x": np.zeros_like(x), "weights": np.zeros_like(weights),
             "w_gate": np.zeros_like(w_gate), "w_up": np.zeros_like(w_up),
             "w_down": np.zeros_like(w_down)}
    for t in range(x.shape[0]):
        for slot in range(weights.shape[1]):
            e, w = int(experts[t, slot]), weights[t, slot]
            g, u = x[t] @ w_gate[e], x[t] @ w_up[e]
            sig = 1.0 / (1.0 + np.exp(-g))
            h = g * sig * u
            out = h @ w_down[e]
            y[t] += w * out
            grads["weights"][t, slot] = out @ dy[t]
            grads["w_down"][e] += w * np.outer(h, dy[t])
            dh = w * (w_down[e] @ dy[t])
            dg, du = dh * u * sig * (1.0 + g * (1.0 - sig)), dh * g * sig
            grads["w_gate"][e] += np.outer(x[t], dg)
            grads["w_up"][e] += np.outer(x[t], du)
            grads["x"][t] += w_gate[e] @ dg + w_up[e] @ du
    return y, grads


def _skewed_layer(n_experts, k):
    """-> x, weights, experts, blk: expert 0 empty, the last with every
    token, and one combine weight exactly 0.0."""
    x, blk, bias = _layer_inputs(n_experts, k)
    x1, router1 = _with_bias(x, blk["router"], bias)
    weights, experts, _ = moe.route(x1, router1, k, False)
    _, _, group_sizes = moe.sort_pairs(experts, n_experts)
    sizes = np.asarray(group_sizes)
    assert sizes[0] == 0 and sizes[-1] == x.shape[0] == sizes.max()
    assert sizes.sum() == x.shape[0] * k
    return x, weights.at[5, 1].set(0.0), experts, blk


@pytest.mark.parametrize("n_experts,k", [(8, 2), (64, 8)])
def test_expert_layer_matches_a_per_token_loop(n_experts, k):
    x, weights, experts, blk = _skewed_layer(n_experts, k)
    got = np.asarray(moe.expert_mlp(x, weights, experts, blk["w_gate"],
                                    blk["w_up"], blk["w_down"])[0])
    want, _ = _per_token_loop(x, weights, experts, blk, np.zeros_like(x))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("n_experts,k", [(8, 2), (64, 8)])
def test_expert_layer_gradients_match_a_per_token_loop(n_experts, k):
    x, weights, experts, blk = _skewed_layer(n_experts, k)
    dy = jax.random.normal(jax.random.key(7), x.shape)
    got = jax.grad(
        lambda x, weights, w_gate, w_up, w_down: jnp.sum(dy * moe.expert_mlp(
            x, weights, experts, w_gate, w_up, w_down)[0]),
        argnums=(0, 1, 2, 3, 4))(x, weights, blk["w_gate"], blk["w_up"],
                                 blk["w_down"])
    _, want = _per_token_loop(x, weights, experts, blk, dy)
    for name, g in zip(("x", "weights", "w_gate", "w_up", "w_down"), got):
        assert np.all(np.isfinite(g)), name
        assert rel_err(g, want[name]) <= 1e-5, name
    # the zero weight's pair gives its expert's matrices and its token's
    # input nothing, and still has a gradient of its own: <out, dy>
    assert want["weights"][5, 1] != 0.0
    # the empty expert's matrices get exactly zero
    assert all(not np.any(np.asarray(g[0])) for g in got[2:])


def _equations(jaxpr, path=()):
    """Every equation, nested ones too, with the primitives it lies under."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, path + (eqn.primitive.name,))


def _loops(jaxpr):
    """The ``while`` equations that are not a kernel's own."""
    return [e for path, e in _equations(jaxpr)
            if e.primitive.name == "while" and "pallas_call" not in path]


def test_remat_recomputes_neither_the_down_projection_nor_the_combine():
    """The guard against the combine weights drifting back into token order:
    then their gradient would read the down-projection's output, and remat
    would run that product and the combine's gather a second time."""
    n_experts, k = 8, 2
    x, weights, experts, blk = _skewed_layer(n_experts, k)
    n, d = x.shape
    layer = jax.checkpoint(lambda *a: moe.expert_mlp(a[0], a[1], experts,
                                                     *a[2:])[0])
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(layer(*a) ** 2), argnums=(0, 1, 2, 3, 4)))(
            x, weights, blk["w_gate"], blk["w_up"], blk["w_down"])
    found = list(_equations(jaxpr.jaxpr))
    kernels = [path for path, e in found if e.primitive.name == "pallas_call"]
    # 3 forward; under remat 2 recomputed (gate, up), 3 dx, 3 dW
    assert len(kernels) == 11
    assert sum("remat2" in path for path in kernels) == 8

    def row_gathers(under):
        return sorted(e.outvars[0].aval.shape for path, e in found
                      if e.primitive.name == "gather" and (
                          "remat2" in path) == under
                      and e.outvars[0].aval.shape[-1] == d)
    # forward: the dispatch, the combine
    assert row_gathers(False) == [(n, k, d), (n * k, d)]
    # backward: the recomputed dispatch and the combine's transpose, both
    # reading an (N, D) source; of the (N, k, D) kind only the dispatch's
    # transpose, no recomputed combine
    assert row_gathers(True) == [(n, k, d), (n * k, d), (n * k, d)]
    assert not any(e.primitive.name.startswith("scatter")
                   and e.outvars[0].aval.shape[0] in (n, n * k)
                   for _, e in found)


# ------------------------------- the routing is decided once a step (PR 48)
#: the expert layers the benchmark's cells run: (scoring, experts, a token's,
#: held, matrices an expert, selection bias, ``norm_topk_prob``)
ROUTED = {
    "softmax-every-expert": ("softmax", 8, 2, 8, 3, False, False),  # OLMoE
    "softmax-a-share": ("softmax", 8, 2, 2, 3, False, True),        # SDAR
    "sigmoid-bias-a-share-two-matrices":                  # Nemotron-3-Nano
        ("sigmoid", 8, 2, 2, 2, True, True),
}
N_TOKENS, D_MODEL = 64, 32


def _routed_layer(case, policy):
    """-> (``loss(h, blk)`` of one such layer, its router losses among it,
    under ``jax.checkpoint`` with ``policy``; the checkpointed layer; h;
    blk), float32."""
    scoring, n_experts, k, held, matrices, biased, norm = ROUTED[case]
    ks = jax.random.split(jax.random.key(0), 6)
    blk = {"router": jax.random.normal(ks[0], (D_MODEL, n_experts)) * 0.5,
           "w_up": jax.random.normal(ks[1], (held, D_MODEL, 16)) * 0.2,
           "w_down": jax.random.normal(ks[2], (held, 16, D_MODEL)) * 0.2}
    if matrices == 3:
        blk["w_gate"] = jax.random.normal(ks[3], (held, D_MODEL, 16)) * 0.2
    h = jax.random.normal(ks[4], (2, N_TOKENS // 2, D_MODEL))
    bias = jax.random.normal(ks[5], (n_experts,)) * 0.1 if biased else None

    def layer(h, blk):
        y, losses, _ = moe.moe_mlp(
            h, blk, experts_per_token=k, norm_topk_prob=norm,
            dtype=jnp.float32, first_held=2 if held < n_experts else 0,
            scoring=scoring, bias=bias,
            activation=jax.nn.silu if matrices == 3 else moe.relu2)
        return y, losses

    layer = jax.checkpoint(layer, policy=policy)

    def loss(h, blk):
        y, (balance, z) = layer(h, blk)
        return jnp.sum(y ** 2) + balance + z

    return loss, layer, h, blk


@pytest.mark.parametrize("case", sorted(ROUTED))
def test_the_layer_keeps_what_its_router_decided(case):
    """Under the plain policy (no device memory, no rung of the ladder) the
    checkpointed layer's residuals are its arguments and the routing's
    arrays, ``moe.routing_bytes`` in all; the scores only where the
    backward reads them (through ``norm_topk_prob``'s division)."""
    _, n_experts, k, *_, norm = ROUTED[case]
    _, layer, h, blk = _routed_layer(case, remat.layer_policy([], 0))
    N = N_TOKENS
    routing = [("float32", (N, n_experts)),         # the logits
               ("int32", (N, k)), ("float32", (N, k)),  # the ids, the scores
               ("int32", (N * k,)), ("int32", (N, k)),  # order, inverse
               ("int32", (n_experts,)),             # group sizes
               ("float32", (N * k,))]               # the weights, sorted
    assert 4 * sum(np.prod(shape) for _, shape in routing) \
        == moe.routing_bytes(N, n_experts, k)
    if not norm:
        routing.remove(("float32", (N, k)))
    arguments = [(str(a.dtype), a.shape) for a in jax.tree.leaves((h, blk))]
    assert sorted((str(aval.dtype), tuple(aval.shape))
                  for aval, _ in saved_residuals(layer, h, blk)) \
        == sorted(arguments + routing)


@pytest.mark.parametrize("case", sorted(ROUTED))
def test_the_backward_routes_nothing_again(case):
    """One ``top_k`` and one full-precision product in the forward, none in
    the checkpoint's second run: the backward holds the product's two
    transposes and, of the sorts, the one that brings the weights' gradient
    back into token order."""
    loss, _, h, blk = _routed_layer(case, remat.layer_policy([], 0))
    found = list(_equations(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1)))(h, blk).jaxpr))

    def count(name, backward, **params):
        return sum(e.primitive.name == name
                   and ("remat2" in path or "checkpoint" in path) == backward
                   and all(str(e.params[key]) == str(value)
                           for key, value in params.items())
                   for path, e in found)

    highest = dict(precision=(jax.lax.Precision.HIGHEST,) * 2)
    assert (count("top_k", False), count("top_k", True)) == (1, 0)
    assert (count("dot_general", False, **highest),
            count("dot_general", True, **highest)) == (1, 2)
    # forward: two argsorts and the weights into expert order
    assert (count("sort", False), count("sort", True)) == (3, 1)


@pytest.mark.parametrize("case", sorted(ROUTED))
def test_keeping_the_routing_changes_no_number(case):
    """Loss equal and every gradient within 1e-6 of the same layer under
    ``save_splash_residuals`` alone, which routes a second time."""
    def run(policy):
        loss, _, h, blk = _routed_layer(case, policy)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(h, blk)

    (want_loss, want), (loss, grads) = (
        run(save_splash_residuals), run(remat.layer_policy([], 0)))
    assert float(loss) == float(want_loss)
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        assert rel_err(got, ref) <= 1e-6


# ----------------------------------------------------------- (c) dropless
def test_dropless_and_token_order_independent():
    n_experts, k = 8, 2
    x, blk, bias = _layer_inputs(n_experts, k, n_tokens=40)
    x1, router1 = _with_bias(x, blk["router"], bias)
    weights, experts, _ = moe.route(x1, router1, k, False)
    order, inverse, group_sizes = moe.sort_pairs(experts, n_experts)
    n_pairs = x.shape[0] * k
    assert int(group_sizes.sum()) == n_pairs
    # no pair lost: the order is a permutation and the inverse inverts it
    np.testing.assert_array_equal(np.sort(np.asarray(order)),
                                  np.arange(n_pairs))
    np.testing.assert_array_equal(
        np.asarray(order)[np.asarray(inverse).reshape(-1)], np.arange(n_pairs))

    def layer(x, weights, experts):
        return moe.expert_mlp(x, weights, experts, blk["w_gate"],
                              blk["w_up"], blk["w_down"])[0]
    base = np.asarray(layer(x, weights, experts))
    perm = np.random.default_rng(0).permutation(x.shape[0])
    shuffled = np.asarray(layer(x[perm], weights[perm], experts[perm]))
    np.testing.assert_allclose(shuffled, base[perm], rtol=1e-6, atol=1e-7)
    # a token whose weights are zeroed contributes nothing and disturbs none
    zeroed = np.asarray(layer(x, weights.at[3].set(0.0), experts))
    assert np.all(zeroed[3] == 0.0)
    np.testing.assert_allclose(np.delete(zeroed, 3, 0),
                               np.delete(base, 3, 0), rtol=1e-6, atol=1e-7)


# ------------------------------------------------- (d) the grouped matmul
@pytest.mark.parametrize("sizes", [(5, 0, 11, 8), (0, 0, 24, 0), (6, 6, 6, 6),
                                   (24, 0, 0, 0)],
                         ids=["ragged", "one-group", "even", "first-only"])
def test_grouped_matmul_three_products_match_einsum_per_group(sizes):
    M, K, N = sum(sizes), 16, 24
    ks = jax.random.split(jax.random.key(2), 3)
    lhs = jax.random.normal(ks[0], (M, K))
    rhs = jax.random.normal(ks[1], (len(sizes), K, N))
    dout = jax.random.normal(ks[2], (M, N))
    group_sizes = jnp.asarray(sizes, jnp.int32)

    out, vjp = jax.vjp(lambda a, b: grouped_matmul(a, b, group_sizes),
                       lhs, rhs)
    dlhs, drhs = vjp(dout)
    starts = np.concatenate([[0], np.cumsum(sizes)])
    for g, (lo, hi) in enumerate(zip(starts, starts[1:])):
        a, d = np.asarray(lhs[lo:hi]), np.asarray(dout[lo:hi])
        np.testing.assert_allclose(
            out[lo:hi], np.einsum("mk,kn->mn", a, rhs[g]), rtol=1e-5,
            atol=1e-5)
        np.testing.assert_allclose(
            dlhs[lo:hi], np.einsum("mn,kn->mk", d, rhs[g]), rtol=1e-5,
            atol=1e-5)
        # an empty group's matrix gets a zero gradient
        np.testing.assert_allclose(
            drhs[g], np.einsum("mk,mn->kn", a, d), rtol=1e-5, atol=1e-5)


def test_grouped_matmul_refuses_a_backend_without_the_kernel(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError, match="megablox"):
        grouped_matmul(jnp.ones((8, 8)), jnp.ones((1, 8, 8)),
                       jnp.asarray([8], jnp.int32))


#: The grouped products of the five cells with experts, as a window (or
#: OLMoE's whole layer) hands them over: cell -> (rows, hidden width, expert
#: width, groups, held groups, the first held): a share's window lies between
#: two groups ``rhs`` does not hold (``models/moe.py:_move_window``).
CELL_PRODUCTS = {
    "olmoe-s4096": (65536, 2048, 1024, 64, 64, 0),
    "sdar-ep8-s8192": (16384, 2048, 768, 18, 16, 1),
    "nemotron-ep16-s8192": (12288, 2688, 1856, 10, 8, 1),
    "solar-open2-ep40-tp8": (8192, 4096, 1280, 10, 8, 1),
    "joyai-ep16-s8192": (8192, 2048, 768, 18, 16, 1),
}
#: cell -> the tiles of gate / up (rows x hidden -> expert width) and of down
#: (back): dx of each asks with the other's roles, dW with its own
CELL_TILES = {
    "olmoe-s4096": ((512, 1024, 1024), (512, 1024, 1024)),
    "sdar-ep8-s8192": ((512, 1024, 768), (512, 768, 1024)),
    "nemotron-ep16-s8192": ((512, 896, 640), (512, 640, 896)),
    "solar-open2-ep40-tp8": ((512, 1024, 640), (512, 640, 1024)),
    "joyai-ep16-s8192": ((512, 1024, 768), (512, 768, 1024)),
}


@pytest.mark.parametrize("product", ["gate_up", "down"])
@pytest.mark.parametrize("cell", sorted(CELL_PRODUCTS))
def test_the_tile_is_a_function_of_the_products_shapes(cell, product):
    """What ``grouped_matmul.tile_for`` returns at every distinct call of the
    five cells: OLMoE's exactly the tile it was timed with, a width over one
    tile walked in the multiple of 128 that overhangs it least (1280 as 2 x
    640, 2688 as 3 x 896, 1856 as 3 x 640), the row tile dividing the rows;
    and the first-call record says so."""
    m, hidden, width, *_ = CELL_PRODUCTS[cell]
    k, n = (hidden, width) if product == "gate_up" else (width, hidden)
    with first_call.noting() as notes:
        tile = tile_for(m, k, n)
    assert tile == CELL_TILES[cell][product == "down"]
    assert notes == {"gmm_tiles": {f"{m}x{k}x{n}": tile}}
    tm, tk, tn = tile
    assert m % tm == 0 and tk <= 1024 and tn <= 1024
    for w, t in ((k, tk), (n, tn)):  # no multiple of 128 overhangs less
        assert t == w or t % 128 == 0
        assert all(-(-w // t) * t <= -(-w // c) * c
                   for c in range(128, 1025, 128))


def test_a_width_under_one_tile_is_its_own_tile():
    assert tile_for(24, 16, 24) == (8, 16, 24)
    assert tile_for(1024, 1000, 1024) == (512, 1000, 1024)
    assert tile_for(512, 1152, 1100) == (512, 384, 384)


@pytest.mark.parametrize("cell", sorted(CELL_PRODUCTS))
def test_grouped_matmul_at_the_cells_widths_and_tiles(cell):
    """The three products (the forward, dx, dW) of gate / up and of down in
    interpret mode against the per-group einsum in float32, at the cell's
    own widths, groups, held run and tiles, the rows cut to three row tiles:
    uneven groups that straddle the row tiles, empty held groups, and,
    where the cell holds a share, rows before and after the held run, which
    come out zero and whose matrices' gradients are not asked for.  (OLMoE's
    64 groups are cut to 8: the interpreter copies all 64 gradients at every
    visit, and the tile, which is the parent's there, does not ask for the
    groups.)"""
    _, hidden, width, G, H, first = CELL_PRODUCTS[cell]
    G, H = (8, 8) if G == H else (G, H)
    rng = np.random.default_rng(G * width)
    M = 3 * 512
    # the held rows over six of the held groups, the others empty
    sizes = np.zeros(H, np.int64)
    sizes[rng.choice(np.arange(H), 6, replace=False)] = rng.multinomial(
        M - 100 * (G - H), np.ones(6) / 6)
    if G > H:  # the window: rows before and after the held run
        sizes = np.concatenate([[37], sizes, [100 * (G - H) - 37]])
    assert sizes.sum() == M and len(sizes) == G
    starts = np.concatenate([[0], np.cumsum(sizes)])
    group_sizes = jnp.asarray(sizes, jnp.int32)
    for K, N in ((hidden, width), (width, hidden)):
        lhs = rng.standard_normal((M, K), np.float32)
        rhs = rng.standard_normal((H, K, N), np.float32) / np.sqrt(K)
        dout = rng.standard_normal((M, N), np.float32)
        with first_call.noting() as notes:
            out, vjp = jax.vjp(
                lambda a, b: grouped_matmul(a, b, group_sizes, first),
                jnp.asarray(lhs), jnp.asarray(rhs))
            dlhs, drhs = vjp(jnp.asarray(dout))
        # the tiles of the cell's own calls, rows apart
        assert set(notes["gmm_tiles"].values()) == {
            tile_for(M, K, N), tile_for(M, N, K)} == {
            (512, *t[1:]) for t in CELL_TILES[cell]}
        want_out, want_dlhs = np.zeros((M, N), np.float32), np.zeros_like(lhs)
        for h in range(H):
            lo, hi = starts[first + h], starts[first + h + 1]
            want_out[lo:hi] = lhs[lo:hi] @ rhs[h]
            want_dlhs[lo:hi] = dout[lo:hi] @ rhs[h].T
            np.testing.assert_allclose(drhs[h], lhs[lo:hi].T @ dout[lo:hi],
                                       rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(out, want_out, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(dlhs, want_dlhs, rtol=2e-4, atol=2e-4)
        for got in (np.asarray(out), np.asarray(dlhs)):  # exactly zero
            assert not got[:starts[first]].any()
            assert not got[starts[first + H]:].any()


@pytest.mark.parametrize("cell", sorted(CELL_PRODUCTS))
def test_grouped_matmul_lowers_for_the_tpu_at_the_cells_shapes(monkeypatch,
                                                               cell):
    """Lowering for the TPU needs no TPU: at each cell's full shapes (OLMoE:
    8192 tokens x 8 experts a token, 64 groups, 2048 -> 1024 and back; a
    share's window: ``rhs`` a run of the groups from the second on) the three
    products become three Mosaic calls, in bf16 with the tiles
    ``grouped_matmul.tile_for`` gives them: a tile the lowering refuses is
    found without a chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    M, D, F, G, H, first = CELL_PRODUCTS[cell]
    group_sizes = jax.ShapeDtypeStruct((G,), jnp.int32)
    for K, N in ((D, F), (F, D)):
        lhs = jax.ShapeDtypeStruct((M, K), jnp.bfloat16)
        rhs = jax.ShapeDtypeStruct((H, K, N), jnp.bfloat16)
        text = jax.jit(jax.value_and_grad(
            lambda a, b, s: jnp.sum(grouped_matmul(a, b, s, first).astype(
                jnp.float32)), (0, 1))).trace(lhs, rhs, group_sizes).lower(
                    lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 3


# ------------------------------------------------- (e) the router losses
def test_router_losses_against_closed_forms():
    E = 4
    # six tokens, k = 2; pairs per expert: 0 -> 5, 1 -> 4, 2 -> 2, 3 -> 1
    experts = jnp.asarray([[0, 1], [0, 1], [0, 1], [0, 1], [0, 2], [3, 2]])
    logits = jnp.log(jnp.asarray([[0.4, 0.3, 0.2, 0.1]] * 5
                                 + [[0.1, 0.2, 0.3, 0.4]])) + 2.0
    probs = jax.nn.softmax(logits, axis=-1)
    balance, z = moe.router_losses(logits, probs, experts)
    f = np.array([5, 4, 2, 1]) / 12.0
    p = (5 * np.array([0.4, 0.3, 0.2, 0.1]) + np.array([0.1, 0.2, 0.3, 0.4])) \
        / 6.0
    assert float(balance) == pytest.approx(E * float(f @ p), rel=1e-6)
    # every row sums to one before the shift, so logsumexp is the shift
    assert float(z) == pytest.approx(4.0, rel=1e-5)
    # uniform router, even load: exactly 1; everything on one expert: E
    even = jnp.asarray([[0, 1], [2, 3]])
    flat = jnp.zeros((2, E))
    assert float(moe.router_losses(
        flat, jax.nn.softmax(flat), even)[0]) == pytest.approx(1.0)
    one = jnp.asarray([[50.0, 0, 0, 0]] * 2)
    assert float(moe.router_losses(
        one, jax.nn.softmax(one), jnp.zeros((2, 1), jnp.int32))[0]) \
        == pytest.approx(E, rel=1e-6)


# -------------------------------------------------------- (f) under a mesh
def _gathered_shapes(hlo_text):
    found = re.findall(r"= \w+\[([\d,]*)\][^\n]* all-gather(?:-start)?\(",
                       hlo_text)
    return sorted(tuple(int(d) for d in dims.split(",") if d)
                  for dims in found)


@pytest.mark.parametrize("axis", ["data", "fsdp"])
def test_four_devices_equal_one_and_only_weights_are_gathered(axis):
    # 8 rows of 96: no count of rows, tokens (768; 192 a device) or pairs is
    # also a dimension of a parameter (8, 64, 128, 1024)
    config = _tiny(dtype=jnp.float32, logits_dtype=jnp.float32, seq_len=96)
    dense = dataclasses.replace(
        config, n_experts=0, experts_per_token=0, qk_norm=False)
    params = llama.init_params(config, jax.random.key(0))
    tokens, targets = _batch(config, rows=8)
    loss1, grads1 = jax.jit(jax.value_and_grad(
        lambda p, t, y: llama.loss_fn(p, t, y, config)))(
            params, tokens, targets)

    mesh = make_mesh(MeshSpec(**{axis: 4}), jax.devices()[:4])

    def compiled_on_mesh(config, params):
        with jax.set_mesh(mesh):
            sharded = jax.device_put(
                params, pytree_sharding(llama.logical_axes(config), mesh))
            t4, y4 = (jax.device_put(a, batch_sharding(mesh))
                      for a in (tokens, targets))
            compiled = jax.jit(jax.value_and_grad(
                lambda p, t, y: llama.loss_fn(p, t, y, config))).lower(
                    sharded, t4, y4).compile()
            return compiled, (sharded, t4, y4)

    compiled, args = compiled_on_mesh(config, params)
    loss4, grads4 = compiled(*args)
    assert float(loss4) == pytest.approx(float(loss1), rel=1e-5)
    errs = jax.tree.map(rel_err, grads4, grads1)
    assert max(jax.tree.leaves(errs)) <= 1e-4, errs

    # What the expert layer and QK-norm add to the dense program's
    # all-gathers: parameters only (under fsdp; a layer's slice of a stacked
    # one keeps a leading 1), never the batch or anything cut like it.
    added = _gathered_shapes(compiled.as_text())
    for shape in _gathered_shapes(compiled_on_mesh(
            dense, llama.init_params(dense, jax.random.key(0)))[0].as_text()):
        if shape in added:
            added.remove(shape)
    if axis == "fsdp":
        assert added, "fsdp=4 stores the experts cut: they have to be gathered"
    weights = {v.shape[1:] for v in params["blocks"].values()} \
        | {params["wte"].shape}
    for shape in added:
        assert shape in weights or (
            shape[0] == 1 and shape[1:] in weights), (
            f"all-gather of {shape}: not a parameter's shape {weights}")


# ------------------------------------------- (g) Mistral's program unchanged
def test_without_experts_the_program_has_no_trace_of_them():
    config = dataclasses.replace(llama.LlamaConfig.tiny(), attn_impl="xla")
    params = jax.eval_shape(
        lambda k: llama.init_params(config, k), jax.random.key(0))
    assert set(params["blocks"]) == {"attn_norm", "wq", "wk", "wv", "wo",
                                     "mlp_norm", "w_gate", "w_up", "w_down"}
    tokens = jax.ShapeDtypeStruct((2, config.seq_len), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t: llama.loss_fn(p, t, t, config))(params, tokens)

    def names(jaxpr):
        return [e.primitive.name for _, e in _equations(jaxpr.jaxpr)]

    dense = names(jaxpr)
    for primitive in ("sort", "top_k", "pallas_call", "shard_map", "while"):
        assert primitive not in dense
    # the embedding lookup and the target pick, nothing else
    assert dense.count("gather") == 2
    def layer_scans(jaxpr):  # the loss's own equations, nothing nested
        return [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]

    # the carry alone: no per-layer output
    assert [len(e.outvars) for e in layer_scans(jaxpr)] == [1]

    moe_config = _tiny()
    moe_params = jax.eval_shape(
        lambda k: llama.init_params(moe_config, k), jax.random.key(0))
    moe_jaxpr = jax.make_jaxpr(
        lambda p, t: llama.loss_fn(p, t, t, moe_config))(moe_params, tokens)
    assert {"sort", "top_k", "pallas_call"} <= set(names(moe_jaxpr))
    # carry, load-balance (L,), z (L,), the held experts' rows (L, 1, E):
    # every expert is held, so no windows (no loop but the kernels' own)
    # and no count of rows moved
    assert [len(e.outvars) for e in layer_scans(moe_jaxpr)] == [4]
    assert not _loops(moe_jaxpr.jaxpr)
    assert set(jax.eval_shape(
        lambda p, t: llama.loss_and_counters(p, t, t, moe_config)[1],
        moe_params, tokens)) == {"moe_rows"}


def test_importing_the_model_imports_what_it_did():
    """What a process pays before its first step: ``import
    ray_tpu.models.llama`` brings in the modules it brought in at PR 38's
    parent (``tests/data/llama_import_modules.json``) and no other of the
    package, and no package it did not."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "tests", "data",
                           "llama_import_modules.json")) as f:
        before = json.load(f)
    done = subprocess.run(
        [sys.executable, "-c", "import json, sys\n"
         "import ray_tpu.models.llama\n"
         "print(json.dumps(sorted(sys.modules)))"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root),
        capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    now = json.loads(done.stdout.strip().splitlines()[-1])
    assert sorted(m for m in now if m.split(".")[0] == "ray_tpu") \
        == before["ray_tpu"]
    assert {m.split(".")[0] for m in now} <= set(before["top_level"])


def test_sizes_follow_the_published_model():
    olmoe = llama.LlamaConfig(
        vocab_size=50304, n_layer=1, n_head=16, n_kv_head=16, d_model=2048,
        d_ff=1024, seq_len=4096, n_experts=64, experts_per_token=8,
        qk_norm=True)
    assert llama.num_params(olmoe) == 625_616_896  # 625.6 M
    shapes = jax.eval_shape(lambda k: llama.init_params(olmoe, k),
                            jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == llama.num_params(olmoe)
    axes = llama.logical_axes(olmoe)
    assert jax.tree.map(len, axes, is_leaf=lambda a: isinstance(a, tuple)) \
        == jax.tree.map(lambda a: len(a.shape), shapes)
    assert axes["blocks"]["w_gate"] == ("layers", "expert", "embed", "mlp")
    # 6 x (attention, router, 8 of 64 experts, embedding + head, norms) +
    # causal attention at S=4096
    active = llama.num_params(olmoe) - 56 * 3 * 2048 * 1024
    assert llama.flops_per_token(olmoe) == 6.0 * active + 12.0 * 2048 * 4096
