"""allenai's Olmo-Hybrid-7B through ``models/hybrid.py``: the gated delta
rule with one decay a head as the token mixer of three layers in four (kind
``G``, ``models/gdn.py`` over ``ops/gdn.py``), attention without rotary
embedding under an RMSNorm over all of q and of k on the fourth, a dense
SwiGLU MLP after every mixer, the norm *after* each sub-layer
(``norm_after``), no experts, an untied head.

The plain reference is ``benchmarks/reference/olmo_hybrid.py``, the one copy
(float32, the recurrence one position after the other, attention a block of
queries at a time).  Everything runs on the CPU with seeded random weights
at tiny sizes, attention on the einsum path.  (``ops/gdn.py`` against the
recurrence alone is ``tests/test_gdn.py``.)  What every family is held to
is ``tests/test_families.py``'s, by the row ``olmo_hybrid``, which states the
leaves to shake before a comparison and why.
"""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import cost_olmo_hybrid, spec
from benchmarks.reference import olmo_hybrid as reference
from benchmarks.reference.llama import _rmsnorm
from ray_tpu.models import attn, dense, gdn, hybrid, mamba2
from ray_tpu.ops import remat
from tests import families
from tests.families import F32_GRAD_TOL, rel_err

FAMILY = "olmo_hybrid"


# ------------------------------------ (1) each kind under either placement
REFERENCE_CFG = {"linear_num_value_heads": 4, "linear_key_head_dim": 12,
                 "num_attention_heads": 4, "num_key_value_heads": 4,
                 "hidden_size": 128, "rms_norm_eps": 1e-6}


def _sub_layer(kind):
    """(the kind's module, its norm's name, the reference's sub-layer on an
    un-normed input)."""
    return {
        "G": (gdn, "gdn_norm",
              lambda u, w: reference.linear_attention(u, w, REFERENCE_CFG)),
        "*": (attn, "attn_norm",
              lambda u, w: reference.full_attention(u, w, REFERENCE_CFG, 64)),
        "D": (dense, "mlp_norm", reference.swiglu),
    }[kind]


@pytest.mark.parametrize("after", [True, False], ids=["after", "before"])
@pytest.mark.parametrize("kind", ["G", "*", "D"])
def test_a_layer_is_the_references_under_either_norm_placement(kind, after):
    """With ``norm_after`` a layer is ``x + norm(f(x))``, without it ``x +
    f(norm(x))``, ``f`` the reference's sub-layer and ``norm`` the kind's
    one weight: forward and every gradient, in float32."""
    module, norm, f = _sub_layer(kind)
    config = families.float32(FAMILY, norm_after=after)
    blk = jax.tree.map(lambda a: a[0], families.shaken(
        FAMILY, {hybrid.KINDS[kind].stack: module.init_params(
            config, jax.random.key(0), 1, 0.02)})[hybrid.KINDS[kind].stack])
    x = jax.random.normal(jax.random.key(2), (2, 64, config.d_model))
    layer = module.layer(config, module.logical_axes(config), 0)
    eps = config.rms_eps

    def ours(x, blk):
        return layer(x, blk)[0]

    def theirs(x, blk):
        if after:
            return x + _rmsnorm(f(x, blk), blk[norm], eps)
        return x + f(_rmsnorm(x, blk[norm], eps), blk)

    probe = jax.random.normal(jax.random.key(3), x.shape)
    with jax.default_matmul_precision("highest"):
        assert rel_err(ours(x, blk), theirs(x, blk)) < 1e-5
        got, want = (jax.grad(lambda x, blk: jnp.sum(g(x, blk) * probe),
                              argnums=(0, 1))(x, blk)
                     for g in (ours, theirs))
    assert set(got[1]) == set(blk)
    for path, err in jax.tree_util.tree_flatten_with_path(
            jax.tree.map(rel_err, got, want))[0]:
        assert err < F32_GRAD_TOL, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("kind", ["M", "K", "E", "L", "W", "C"])
def test_a_kind_that_does_not_read_norm_after_refuses_it(kind):
    """Only ``*``, ``D`` and ``G`` read the field; a pattern that holds any
    other kind under it is refused when the configuration is made, and a
    kind's module says which it is (``NORM_AFTER``)."""
    assert not hasattr(hybrid.KINDS[kind].module, "NORM_AFTER")
    with pytest.raises(ValueError, match="norm_after over the kinds"):
        hybrid.HybridConfig(pattern="GD" + kind, norm_after=True)
    hybrid.HybridConfig(pattern="GD" + kind)  # without it, any pattern
    assert [k for k, entry in hybrid.KINDS.items()
            if getattr(entry.module, "NORM_AFTER", False)] == ["*", "D", "G"]


# ------------------------------------------------------ (2) the whole model
def test_bf16_master_weights_would_fail_the_float32_tolerance():
    """The float32 comparison is tight enough to see one rounding of the
    weights: the float32 program on parameters rounded to bfloat16 misses
    the reference on the parameters as drawn, by the gradients' limit."""
    exact = families.compared(FAMILY, "float32")
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
        exact["params"])
    _, grads = families.program(FAMILY, "float32")(
        rounded, exact["tokens"], exact["targets"])
    ref_grads = exact["ref_grads"]
    worst = max(jax.tree.leaves(jax.tree.map(rel_err, grads, ref_grads)))
    assert worst > 5 * F32_GRAD_TOL, worst


def test_packed_documents_restart_neither_state_nor_convolution():
    """A row is one sequence to the recurrence and to the taps: a ``G``
    layer's output at a row's last position reads the row's first, across
    whatever document boundaries the ids spell, in the program and in the
    reference alike (no scan takes segment ids: ROADMAP B)."""
    config = families.float32(FAMILY)
    blk = jax.tree.map(lambda a: a[0], gdn.init_params(
        config, jax.random.key(0), 1, 0.02))
    layer = gdn.layer(config, gdn.logical_axes(config), 0)
    x = jax.random.normal(jax.random.key(1), (1, 128, config.d_model))
    moved = x.at[0, 0].add(1.0)
    for f in (lambda x: layer(x, blk)[0],
              lambda x: reference.linear_attention(x, blk, REFERENCE_CFG)):
        assert float(jnp.abs(f(moved)[0, -1] - f(x)[0, -1]).max()) > 0


# ------------------------------------------- (3) counts, sizes, the kind
@pytest.mark.parametrize("name,seq_len", [("tiny-olmo-hybrid", 128),
                                          ("olmo-hybrid-7b-l4", 8192)])
def test_num_params_and_flops_are_the_adapters_and_the_cost_files(name,
                                                                  seq_len):
    """A pattern without ``E``: ``num_params`` counts the leaves that
    exist, ``flops_per_token`` is the yardstick's own count
    (``lib/cost_olmo_hybrid.py``), and the kind's ``mixer_flops`` the cost
    file's scan."""
    config = spec.load_json(spec.BENCH_DIR, "configs", name + ".json")
    module = spec.load_module("models", "olmo_hybrid")
    _, model = module.model_config(config, seq_len)
    family = module.build(config, seq_len)
    shapes = jax.eval_shape(family.init_fn, jax.random.key(0))
    assert hybrid.num_params(model) \
        == sum(a.size for a in jax.tree.leaves(shapes)) \
        == cost_olmo_hybrid.params_held(config)
    assert family.flops_per_token == hybrid.flops_per_token(model) \
        == cost_olmo_hybrid.model_flops_per_token(config, seq_len)
    assert gdn.mixer_flops(model, seq_len) \
        == cost_olmo_hybrid.scan_flops_per_position(config, seq_len)
    met = cost_olmo_hybrid.layer_matmul_params(config)
    assert (gdn.matmul_params(model, 0), attn.matmul_params(model, 0),
            dense.matmul_params(model, 0)) \
        == (met["linear"], met["full"], met["mlp"])


def test_the_remat_rule_is_offered_a_pattern_without_experts():
    """``_layer_sizes`` of a stack with no ``E``: q, k and v from ``*``,
    gate and up from the four ``D`` layers, the three ``G`` layers' inverse
    and the projections their convolutions read, no routing, and a bound
    that holds the widest layer's working set, the ``G`` layer's among
    them (XLA's form here: heads of 12 under 24 are not the kernels')."""
    config = families.preset(FAMILY)
    shapes = jax.eval_shape(lambda: hybrid.init_params(config,
                                                       jax.random.key(0)))
    tokens, item = 2 * 128, 2
    candidates, bound = hybrid._layer_sizes(shapes, (2, 128, 128), config)
    assert families.named(candidates) == {
        remat.QKV: tokens * 3 * 128 * item,
        remat.GATE_UP: 4 * 2 * tokens * 256 * item,
        remat.INVERSE: 3 * tokens * 4 * 32 * item,
        remat.CONV_IN: 3 * tokens * 4 * (2 * 12 + 24) * item}
    assert {(rung.group, rung.layers) for rung in candidates} \
        == {("*", 1), ("D", 4), ("G", 3)}
    working, kept, named = gdn.layer_bytes(config, tokens, 128, 1, item)
    assert kept == 0 and set(named) == {remat.INVERSE, remat.CONV_IN}
    assert working == tokens * 4 * (10 * 12 * 2 + 12 * 24 * 2
                                    + 2 * 32 * (16 + 4))
    assert working > dense.layer_bytes(config, tokens, 128, 1, item)[0]
    params = sum(4 * a.size for a in jax.tree.leaves(shapes))
    # after the last layer every gradient stands; a G layer's backward
    # holds its working set beside the other layers'
    assert bound > max(params, working) + 8 * hybrid.PACKING
    # on a device that reports room the rule keeps every rung, the inverse
    # first (it spares most a byte), every layer of each
    decision = remat.choose(4 << 30, 0, candidates, bound)
    assert decision.names[0] == remat.INVERSE
    assert set(decision.names) == {remat.INVERSE, remat.QKV, remat.GATE_UP,
                                   remat.CONV_IN}
    assert all(kept == of for _, _, kept, of in decision.kept)


def test_the_delta_rule_kind_counts_itself_and_borrows_its_convolution():
    """The kind's parameters by hand; its convolution is
    ``mamba2.short_conv`` (``causal_conv`` under its silu, or
    ``ops/conv_kernel.py``'s pass: PR 61) and its scan ``ops/gdn.py``'s."""
    config = families.preset(FAMILY)
    D, H, dk, dv = 128, 4, 12, 24
    assert gdn.num_params(config) == D * H * (2 * dk + 3 * dv + 2) \
        + 4 * H * (2 * dk + dv) + 2 * H + dv + D
    assert hybrid.KINDS["G"].module is gdn
    assert gdn.short_conv is mamba2.short_conv
    assert "def causal_conv" not in open(gdn.__file__).read() \
        and "def short_conv" not in open(gdn.__file__).read()
