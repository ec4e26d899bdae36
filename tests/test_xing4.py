"""XingChen-AGI's Xing4.0-29B-A4B through ``models/hybrid.py``: a residual
of four streams under manifold-constrained hyper-connections
(``models/streams.py``: a sub-layer's read, its write and a stream map that
twenty Sinkhorn turns normalise), over the latent attention, the experts and
the prediction module of ``tests/test_joyai.py``, with YaRN under the latent
attention's rotary lanes.

The plain reference is ``benchmarks/reference/xing4_0.py``.  Everything runs
on the CPU with seeded random weights at tiny sizes, attention on the einsum
path.  What every family is held to is ``tests/test_families.py``'s, by the
row ``xing4_0``.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec
from benchmarks.reference import xing4_0 as reference
from benchmarks.reference.llama import _rmsnorm
from ray_tpu.models import experts, hybrid, mla, streams
from ray_tpu.models.layers import Yarn
from ray_tpu.ops import remat
from ray_tpu.util import first_call, tracing
from tests import families
from tests.families import rel_err

FAMILY = "xing4_0"
CONFIG = "xing4.0-29b-a4b-l5-ep8"


def _tiny_file():
    return spec.load_json(spec.BENCH_DIR, "configs", "tiny-xing4.json")


def _one_row(config, seed=0, logits=1.0):
    """A sub-layer's row of ``hc`` whose maps' logits are a few units wide
    (normal(``logits``) from the product beside biases of normal(1): +-4
    over a row's 128 positions), and the streams it reads."""
    hc = jax.tree.map(lambda a: a[0], streams.init_params(
        config, jax.random.key(seed), 1))
    hc["phi"] = hc["phi"] * logits / (0.02 * math.sqrt(hc["phi"].shape[0]))
    hc["alpha"] = jnp.ones((3,))
    hc["base"] = jax.random.normal(jax.random.key(seed + 1),
                                   hc["base"].shape)
    X = tuple(jax.random.normal(jax.random.key(seed + 2),
                                (config.streams, 2, 64, config.d_model)))
    return hc, X


def _as_reference(X):
    """The program's tuple of streams as the reference's (b, S, n, C)."""
    return jnp.stack(X, axis=2)


# --------------------------------------------------------- (1) the maps
def test_twenty_turns_reach_the_manifold_and_match_the_references_loop():
    """At logits a few units wide the stream map's rows and columns sum to 1
    within 1e-4 after ``hc_sinkhorn_iters`` turns, the counter says so, and
    the program's three maps are the reference's explicit loop's.  (The
    turns converge linearly, slower the wider the logits: at normal(3) the
    worst of 128 positions is 3 % off after twenty, which is what the
    counter is for.)"""
    config = families.float32(FAMILY)
    assert (config.streams, config.hc_sinkhorn_iters) == (4, 20)
    hc, X = _one_row(config)
    with jax.default_matmul_precision("highest"):
        H, res = streams.maps(X, hc, config)
        want = reference.stream_maps(_as_reference(X), hc, _tiny_file())
    assert H.shape == (2, 64, 24) and res.shape == (4, 4, 128)
    pre, post, mixed = H[..., :4], H[..., 4:8], H[..., 8:].reshape(
        2, 64, 4, 4)
    assert float(jnp.std(jnp.log(res))) > 1.0  # far from uniform
    for axis in (0, 1):
        assert float(jnp.max(jnp.abs(res.sum(axis) - 1.0))) < 1e-4
    assert float(streams.sinkhorn_err(res)) < 1e-4
    wide = streams.sinkhorn(3.0 * jax.random.normal(
        jax.random.key(4), (4, 4, 128)), 20, 1e-6, (-30.0, 30.0))
    assert 1e-3 < float(streams.sinkhorn_err(wide)) < 0.2
    assert np.array_equal(mixed.reshape(128, 4, 4),
                          jnp.moveaxis(res, 2, 0))
    for got, ref in zip((pre, post, mixed), want):
        assert rel_err(got, ref) < 1e-5
    assert 0 < float(pre.min()) and float(pre.max()) < 1
    assert 0 < float(post.min()) and float(post.max()) < 2


def test_the_clamp_binds_at_logits_of_forty():
    """Logits of +-40 give the matrix of +-30: the clamp, and not the
    exponential's range, bounds what the turns start from."""
    at = jnp.where(jnp.eye(4, dtype=bool), 40.0, -40.0)[:, :, None]
    clamped = jnp.where(jnp.eye(4, dtype=bool), 30.0, -30.0)[:, :, None]
    how = (20, 1e-6, (-30.0, 30.0))
    assert np.array_equal(streams.sinkhorn(at, *how),
                          streams.sinkhorn(clamped, *how))
    free = streams.sinkhorn(at, 20, 1e-6, (-50.0, 50.0))
    assert not np.array_equal(free, streams.sinkhorn(at, *how))
    # and the counter sees a map that the turns did not normalise
    lopsided = jnp.zeros((4, 4, 1)).at[0, 1].set(8.0)
    short = streams.sinkhorn(lopsided, 1, 1e-6, (-30.0, 30.0))
    assert float(streams.sinkhorn_err(short)) > 0.1


@pytest.mark.parametrize("kind", ["L", "D", "E"])
def test_a_sublayer_is_the_references_under_the_maps(kind):
    """One sub-layer of each kind the model runs, ``H_res X + H_post^T
    f(norm(H_pre X))`` against the reference's ``hyper`` in float32: the
    output and the gradient of the streams and of the maps' three leaves."""
    config = families.float32(FAMILY, experts_held=None)
    cfg = dict(_tiny_file(), experts_held=[0, 16])
    entry = hybrid.KINDS[kind]
    blk = jax.tree.map(lambda a: a[0] * 3.0, entry.module.init_params(
        config, jax.random.key(2), 1, 0.02))
    hc, X = _one_row(config, seed=3)
    dX = jax.random.normal(jax.random.key(9), (4, *X[0].shape))
    layer = streams.layer(config, entry.module.branch(
        config, entry.module.logical_axes(config), 0))
    eps = config.rms_eps
    plain = {
        "L": lambda u: reference.attention(
            _rmsnorm(u, blk["attn_norm"], eps), blk, cfg, 32),
        "D": lambda u: reference.swiglu(
            _rmsnorm(u, blk["mlp_norm"], eps), blk["w_gate"], blk["w_up"],
            blk["w_down"]),
        "E": lambda u: reference.experts(
            _rmsnorm(u, blk["mlp_norm"], eps).reshape(128, -1), blk, cfg,
            0).reshape(u.shape)}[kind]

    def ours(X, hc):
        return jnp.vdot(jnp.stack(layer(X, blk, hc)[0]), dX)

    def theirs(X, hc):
        out = reference.hyper(_as_reference(X), hc, cfg, plain)
        return jnp.vdot(jnp.moveaxis(out, 2, 0), dX)

    with jax.default_matmul_precision("highest"):
        got, (gX, ghc) = jax.value_and_grad(ours, (0, 1))(X, hc)
        want, (wX, whc) = jax.value_and_grad(theirs, (0, 1))(X, hc)
        counted = layer(X, blk, hc)[1]
    assert rel_err(got, want) < 1e-5
    assert rel_err(jnp.stack(gX), jnp.stack(wX)) < 2e-4
    for leaf in ("phi", "alpha", "base"):
        assert rel_err(ghc[leaf], whc[leaf]) < 2e-4, leaf
    # every expert is held here: nothing moves through a window
    assert set(counted) == {"mhc_sinkhorn_err"} | (
        {"moe_rows"} if kind == "E" else set())


# ------------------------------- (2) the mechanism reduces to the residual
def test_identity_maps_are_the_one_stream_model():
    """With ``phi`` 0, a read that is the streams' mean, a write of weight 1
    and a stream map at the clamp (the identity to float32) every stream
    stays a copy of the one-stream model's ``x``: both losses are those of
    the same parameters run with ``streams`` 1 (the final norms see ``n x``,
    which only their eps tells from ``x``)."""
    config = families.float32(FAMILY)
    n = config.streams
    params = families.shaken(FAMILY, families.drawn(FAMILY))
    rows = params["hc"]["phi"].shape[0]
    assert rows == len(config.sublayers) == 8
    base = np.concatenate([
        np.full(n, -math.log(n - 1)), np.zeros(n),
        np.where(np.eye(n, dtype=bool), 30.0, -30.0).ravel()])
    params["hc"] = {"phi": jnp.zeros_like(params["hc"]["phi"]),
                    "alpha": params["hc"]["alpha"],
                    "base": jnp.tile(jnp.asarray(base, jnp.float32),
                                     (rows, 1))}
    plain = dataclasses.replace(config, streams=1)
    alone = {k: v for k, v in params.items() if k != "hc"}
    tokens, targets = families.rows(config.vocab_size)
    _, counts = jax.jit(lambda p: hybrid.loss_and_counters(
        p, tokens, targets, config))(params)
    _, want = jax.jit(lambda p: hybrid.loss_and_counters(
        p, tokens, targets, plain))(alone)
    for name in ("loss_main", "loss_mtp"):
        assert rel_err(counts[name], want[name]) < 1e-5, name
    assert np.array_equal(counts["moe_rows"], want["moe_rows"])
    assert "mhc_sinkhorn_err" not in want
    assert counts["mhc_sinkhorn_err"].shape == (8,)
    assert float(counts["mhc_sinkhorn_err"].max()) < 1e-5


def test_one_stream_traces_none_of_it():
    """A configuration with ``streams`` 1 has no ``hc`` stack, opens none of
    the three scopes, leaves no counter and notes no fact of the maps; one
    with streams has them all, and offers the maps to the remat rule in
    front of q, k and v."""
    ids = jax.ShapeDtypeStruct((2, families.SEQ_LEN), jnp.int32)
    seen = {}
    for n in (1, 4):
        config = families.preset(FAMILY, attn_impl="xla", streams=n)
        shapes = jax.eval_shape(lambda: hybrid.init_params(
            config, jax.random.key(0)))
        with first_call.noting() as notes:
            text = jax.jit(jax.grad(lambda p, t: hybrid.loss_fn(
                p, t, t, config))).lower(shapes, ids).as_text(
                    debug_info=True)
            counts = jax.eval_shape(lambda p, t: hybrid.loss_and_counters(
                p, t, t, config)[1], shapes, ids)
        seen[n] = ("hc" in shapes, "mhc" in text, set(counts), notes)
        names = list(families.named(hybrid._layer_sizes(
            shapes, (2, families.SEQ_LEN, config.d_model), config)[0]))
        assert names == ([remat.MAPS] if n > 1 else []) + [
            remat.GATE_UP, remat.LATENTS, remat.QKV, remat.ROUTING]
    assert seen[1][:2] == (False, False)
    assert "mhc_sinkhorn_err" not in seen[1][2]
    assert not {"streams", "hc_sinkhorn_iters", "mhc_sublayers"} \
        & set(seen[1][3])
    assert seen[4][:2] == (True, True)
    assert "mhc_sinkhorn_err" in seen[4][2]
    assert (seen[4][3]["streams"], seen[4][3]["hc_sinkhorn_iters"],
            seen[4][3]["mhc_sublayers"]) == (4, 20, 8)
    for scope in ("mhc", "mhc_maps", "mhc_mix"):
        assert scope in tracing.SCOPE_REGISTRY
    assert "mhc_sinkhorn_err" in tracing.STEP_COUNTER_REGISTRY


@pytest.mark.parametrize("pattern", ["*E", "MD", "LDWE"])
def test_streams_over_a_kind_without_a_branch_are_refused(pattern):
    """As ``norm_after`` is over a kind that does not read it: no kind grows
    a path no model uses."""
    with pytest.raises(ValueError, match="no\n?.*branch|have no"):
        hybrid.HybridConfig(pattern=pattern, streams=4)
    hybrid.HybridConfig(pattern=pattern)  # and is built without them
    assert [kind for kind, entry in hybrid.KINDS.items()
            if hasattr(entry.module, "branch")] == ["E", "L", "D"]


# ------------------------------------------ (3) the share ties to the model
def test_the_shares_of_the_experts_add_up_under_the_maps():
    """A sub-layer's output over the shares [0, 4), [4, 8), [8, 12), [12,
    16) of the tiny preset's 16 experts, the part every chip computes alike
    (the streams' own mix and the shared expert's write) counted once, sums
    to the reference's sub-layer with every expert held."""
    whole = families.float32(FAMILY, experts_held=None)
    cfg = dict(_tiny_file(), experts_held=[0, 16])
    blk = jax.tree.map(lambda a: a[0], experts.init_params(
        whole, jax.random.key(0), 1, 0.02))
    blk["router"] = blk["router"] * 20.0
    hc, X = _one_row(whole, seed=5)
    eps = whole.rms_eps

    def part(first, stop):
        config = families.float32(FAMILY, experts_held=range(first, stop))
        held = dict(blk, **{name: blk[name][first:stop]
                            for name in ("w_gate", "w_up", "w_down")})
        layer = streams.layer(config, experts.branch(
            config, experts.logical_axes(config), 0))
        return _as_reference(layer(X, held, hc)[0])

    def shared(u):
        return reference.swiglu(_rmsnorm(u, blk["mlp_norm"], eps),
                                blk["shared_gate"], blk["shared_up"],
                                blk["shared_down"])

    def uncut(u):
        return reference.experts(
            _rmsnorm(u, blk["mlp_norm"], eps).reshape(128, -1), blk, cfg,
            0).reshape(u.shape)

    with jax.default_matmul_precision("highest"):
        parts = [part(first, first + 4) for first in range(0, 16, 4)]
        alike = reference.hyper(_as_reference(X), hc, cfg, shared)
        want = reference.hyper(_as_reference(X), hc, cfg, uncut)
    assert rel_err(sum(parts) - 3 * alike, want) < 1e-4
    # no share is all: what each adds to the part computed alike
    assert all(rel_err(p - alike, want - alike) > 0.05 for p in parts)


# ------------------------------------------- (4) YaRN under latent attention
def _yarn_by_transformers(dim, base, factor, original, beta_fast, beta_slow):
    """``transformers``' ``_compute_yarn_parameters``, written out."""
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / max(high - low, 0.001), 0, 1)
    extrapolation_factor = 1 - ramp
    return interpolation * (1 - extrapolation_factor) \
        + extrapolation * extrapolation_factor


def test_the_cells_rotary_table_and_softmax_scale():
    """The configuration's 32 rotary frequencies are YaRN's at factor 64
    over 4096 positions, cos and sin unscaled (``mscale`` =
    ``mscale_all_dim``), and the softmax's scale is ``192^-1/2 x (0.1 ln 64
    + 1)^2`` = 0.14468."""
    c = spec.load_json(spec.BENCH_DIR, "configs", CONFIG + ".json")
    _, model = spec.load_module("models", FAMILY).model_config(c, 4096)
    yarn = model.mla_rope_yarn
    assert yarn == Yarn(factor=64.0, original=4096, beta_fast=32.0,
                        beta_slow=1.0, attention_factor=1.0)
    want = _yarn_by_transformers(64, 1e4, 64, 4096, 32, 1)
    table = np.asarray(yarn.inv_freq(64, model.mla_rope_theta))
    assert table.shape == (32,) and np.allclose(table, want, rtol=1e-6)
    assert np.allclose(table, reference.yarn_inv_freq(
        64, 1e4, c["rope_scaling"]), rtol=1e-6)
    # the fastest lanes turn as published, the slowest 64 times slower
    plain = 1e4 ** (-np.arange(0, 64, 2) / 64)
    assert np.allclose(table[:10], plain[:10], rtol=1e-6)
    assert np.allclose(table[-5:] * 64, plain[-5:], rtol=1e-5)
    assert yarn.scale == 1.0
    assert round(model.mla_sm_scale, 5) == 0.14468
    assert model.mla_sm_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    assert (model.streams, model.hc_sinkhorn_iters, model.hc_eps,
            model.hc_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert model.pattern == "LD" + "LE" * 4 and model.mtp_kinds == "LE"


def test_the_mixer_under_yarn_matches_the_written_out_formula():
    """The ``L`` branch against the reference's attention in float32 with
    the tiny preset's YaRN (factor 4 over 32 positions: three of the four
    pairs stretched) and its softmax scale: the output and every leaf's
    gradient; without the two fields the mixer is another function."""
    config = families.float32(FAMILY)
    cfg = _tiny_file()
    assert config.mla_rope_yarn.inv_freq(8, 1e4) == pytest.approx(
        (1.0, 0.025, 0.0025, 0.00025))
    assert config.mla_sm_scale == pytest.approx(
        24 ** -0.5 * (0.1 * math.log(4) + 1) ** 2)
    blk = jax.tree.map(lambda a: a[0] * 6.0, mla.init_params(
        config, jax.random.key(0), 1, 0.02))
    u = jax.random.normal(jax.random.key(1), (2, 64, config.d_model))
    do = jax.random.normal(jax.random.key(2), u.shape)
    branch = mla.branch(config, mla.logical_axes(config), 0)

    def ours(blk, u):
        return jnp.vdot(branch(u, blk)[0], do)

    def theirs(blk, u):
        return jnp.vdot(reference.attention(
            _rmsnorm(u, blk["attn_norm"], config.rms_eps), blk, cfg, 32), do)

    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(ours, (0, 1))(blk, u)
        want, ref_grads = jax.value_and_grad(theirs, (0, 1))(blk, u)
        bare = ours(blk, u), jnp.vdot(mla.branch(dataclasses.replace(
            config, mla_rope_yarn=None, mla_sm_scale=None),
            mla.logical_axes(config), 0)(u, blk)[0], do)
    assert rel_err(got, want) < 1e-5
    for path, err in jax.tree_util.tree_flatten_with_path(
            jax.tree.map(rel_err, grads, ref_grads))[0]:
        assert err < 2e-4, jax.tree_util.keystr(path)
    assert rel_err(bare[1], bare[0]) > 0.01
    # the mixer is the branch and the add
    x = mla.mixer(u, blk, config, mla.logical_axes(config))
    assert rel_err(x - u, branch(u, blk)[0]) < 1e-6


# ------------------------------------------------------ (5) the whole model
def test_the_step_leaves_the_counter_a_row_a_sublayer():
    """Through ``make_train_step``: the step counters are the expert
    layers', the module's two losses and ``mhc_sinkhorn_err``, float32
    (sub-layers,): the pattern's six and the module's two.  (That the
    pattern trains is the cell's rehearsal, ``benchmarks/tests``; one step
    through ``TrainStep`` is ``tests/test_step_names.py``'s, by
    ``hybrid-mhc``.)"""
    config = families.preset(FAMILY, attn_impl="xla")
    optimizer = hybrid.make_optimizer()
    params = jax.eval_shape(lambda: hybrid.init_params(config,
                                                       jax.random.key(0)))
    opt_state = jax.eval_shape(optimizer.init, params)
    step = hybrid.make_train_step(config, optimizer)
    ids = jax.ShapeDtypeStruct((2, families.SEQ_LEN), jnp.int32)
    jax.eval_shape(step, params, opt_state, ids, ids)
    assert set(step.counters) == {"moe_rows", "moe_moved", "loss_main",
                                  "loss_mtp", "mhc_sinkhorn_err"}
    err = step.counters["mhc_sinkhorn_err"]
    assert err.shape == (8,) and err.dtype == jnp.float32
    assert step.counters["moe_rows"].shape == (3, 1, 4)


def test_the_remat_rule_is_given_the_streams_sizes():
    """A kept input is ``streams`` times as wide, the maps are 24 float32 a
    position a sub-layer in front of the ladder, and where the chip has the
    room the rule keeps them first."""
    config = families.preset(FAMILY, attn_impl="xla")
    plain = dataclasses.replace(config, streams=1)
    tokens = 2 * families.SEQ_LEN
    sizes = {}
    for name, c in (("streams", config), ("plain", plain)):
        shapes = jax.eval_shape(lambda: hybrid.init_params(
            c, jax.random.key(0)))
        sizes[name] = hybrid._layer_sizes(
            shapes, (2, families.SEQ_LEN, c.d_model), c)
    with_maps, without = (families.named(sizes[name][0])
                          for name in ("streams", "plain"))
    with_maps_bytes = with_maps.pop(remat.MAPS)
    assert with_maps_bytes == 8 * tokens * 24 * 4
    assert with_maps == without
    # around the head, this size's fullest moment: every sub-layer's kept
    # input three streams wider, and the maps' float32 gradients
    kept = 8 * tokens * 64 * 2
    assert sizes["streams"][1] - sizes["plain"][1] >= 3 * kept
    rungs = sizes["streams"][0][:-1]
    decision = remat.choose(16 << 30, 0, rungs, sizes["streams"][1])
    assert decision.names[0] == remat.MAPS and decision.kept[0] == (
        "hc", remat.MAPS, 8, 8)
    assert set(decision.names) == {remat.MAPS, remat.LATENTS, remat.QKV,
                                   remat.GATE_UP}
    # room for five sub-layers' maps and nothing behind them: the maps
    # spare most a byte, and the climb ends at the rung not kept whole
    a_layer = with_maps_bytes // 8
    tight = remat.choose(10 * (5 * a_layer + a_layer // 2) // 9, 0, rungs, 0)
    assert tight.kept == (("hc", remat.MAPS, 5, 8),)
