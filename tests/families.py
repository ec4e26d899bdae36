"""The model families that have a rehearsal file, one row each, and what the
tests of a family share.

A family is a rehearsal file under ``benchmarks/configs/``, the adapter under
``benchmarks/models/`` that builds the program and the plain reference from
it, and a few facts: which leaves to move before a comparison, which stacks
the gradient tree holds, two pairs of tolerances, the control's limit, the
scopes its kinds own and the first-call values it is about.  ``FAMILIES``
holds them as data; ``tests/test_families.py`` holds each conformance test
once, a case a row.  What is about one family alone (a band's mask, a rotary
pairing, a scan against its recurrence) stays in that family's file, which
imports the helpers here.

A new family is a row here, a line of ``tests/data/lowered_steps.json`` and
the tests of what only it has.

Within a process, what two cases of a row both need is built once
(:func:`built`, :func:`drawn`, :func:`program`, :func:`reference`,
:func:`compared`); ``tests/conftest.py`` puts a family's cases of
``test_families.py`` side by side, so that one worker runs most of them.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, FrozenSet, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import spec

#: every rehearsal file is read at this sequence length
SEQ_LEN = 128
#: benchmarks/lib/correct.py's, which the bf16 program is held to on the chip
LOSS_TOL, GRAD_TOL = 1e-3, 0.75
#: the float32 program against the float32 reference: the same mathematics
#: in another order, float32 summation order only
F32_LOSS_TOL, F32_GRAD_TOL = 1e-5, 2e-4


@dataclasses.dataclass(frozen=True)
class Control:
    """The 8-bit control (``benchmarks/tools/control.py``) at the tiny size:
    the limit it is refused under with room on both sides, on these seeds'
    rows; whether the program as a whole passes there (at the sizes where an
    expert's gradient is a sum over a handful of rows only its median
    does), and then which leaves the leaf limit is held over."""
    limit: float
    seeds: Tuple[int, ...] = (0,)
    program_ok: bool = False
    #: the program's leaves outside this stack lie under the leaf limit and
    #: the control's largest leaf over it; None: not looked at
    leaves_outside: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class Family:
    #: the adapter ``benchmarks/models/<name>.py``, and the case id
    name: str
    #: the rehearsal file ``benchmarks/configs/<preset>.json``
    preset: str
    #: ``models/hybrid.py``'s letters; None: a row of ``models/llama.py``
    pattern: Optional[str]
    #: ``stack.leaf`` -> the factor it is scaled by before a comparison: a
    #: router that prefers some experts, softmaxes far from uniform, decays
    #: that matter
    scale: Mapping[str, float]
    #: the gradient tree's stacks
    stacks: FrozenSet[str]
    #: ``stack.leaf`` (or a leaf of no stack) moved off its start by 0.2
    #: normal: norms and taps that are no identity
    noise: Tuple[str, ...] = ()
    #: what of ``scale`` the bfloat16 comparison takes otherwise
    scale_bfloat16: Mapping[str, float] = dataclasses.field(
        default_factory=dict)
    #: stack -> the leaves its gradient holds, all of them
    leaves: Mapping[str, FrozenSet[str]] = dataclasses.field(
        default_factory=dict)
    #: stack -> leaves its gradient holds among others
    leaves_among: Mapping[str, FrozenSet[str]] = dataclasses.field(
        default_factory=dict)
    #: (loss, gradient leaf) limits of the float32 comparison
    f32_tol: Tuple[float, float] = (F32_LOSS_TOL, F32_GRAD_TOL)
    #: the reference's block of queries
    q_block: int = 64
    control: Optional[Control] = None
    #: scope paths the lowered gradient holds, and holds none of; the scopes
    #: ``tracing.SCOPE_REGISTRY`` lists for the family's kinds
    scopes: Tuple[str, ...] = ()
    no_scopes: Tuple[str, ...] = ()
    registered: Tuple[str, ...] = ()
    #: the first-call values the family is about (not ``gmm_tiles``,
    #: ``moe_return``, ``remat_*``: their own tests hold those)
    first_call: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    #: ``flops_per_token`` of the preset, written out by hand; None: the
    #: family's cost file is its test's (``test_olmo_hybrid.py``) or it has
    #: none at this size
    flops: Optional[float] = None


_ATTN = frozenset({"attn_norm", "wq", "wk", "wv", "wo"})
_BLOCKS = {"blocks": _ATTN | {"q_norm", "k_norm", "mlp_norm", "router",
                              "w_gate", "w_up", "w_down"}}
_EXPERTS = {"experts_held": 4, "experts_total": 16,
            "router_scoring": "sigmoid"}


def _joyai_flops():
    D, S, H = 64, 128, 2
    latent = D * 48 + 48 * H * 24 + D * 40 + 32 * H * 32 + H * 16 * D
    experts = D * 16 + 3 * D * 48 * (1 + 2 * 4 / 16)
    # four latent layers and three expert layers with the module's, a dense
    # layer, the head twice and ``w_eh``
    return 6.0 * (4 * latent + 3 * D * 128 + 3 * experts + 2 * 512 * D
                  + 2 * D * D) + 3.0 * 4 * H * (24 + 16) * S


def _xing4_flops():
    D, S, H, n = 64, 128, 2, 4
    latent = D * 48 + 48 * H * 24 + D * 40 + 32 * H * 32 + H * 16 * D
    experts = D * 16 + 3 * D * 48 * (1 + 2 * 4 / 16)
    maps = n * D * (n * n + 2 * n)
    # tiny-joyai's layers; ``w_eh`` meets the embedding once and the hidden
    # state a stream; eight sub-layers' maps
    return 6.0 * (4 * latent + 3 * D * 128 + 3 * experts + 2 * 512 * D
                  + (1 + n) * D * D + 8 * maps) + 3.0 * 4 * H * (24 + 16) * S


def _laguna_flops():
    D, S, hd, w = 64, 128, 16, 16
    full = D * hd * (2 * 4 + 2 * 2) + D * 4
    band = D * hd * (2 * 6 + 2 * 2) + D * 6
    experts = D * 16 + 3 * D * 48 * (1 + 2 * 4 / 16)
    # a window layer is charged the band's pairs, w - w (w - 1) / 2S a
    # position, and not the triangle's S / 2
    return 6.0 * (2 * full + 3 * band + 3 * D * 128 + 4 * experts
                  + 512 * D) + 3.0 * (
        2 * 4.0 * 4 * hd * S / 2
        + 3 * 4.0 * 6 * hd * (w - w * (w - 1) / (2 * S)))


def _lfm2_flops():
    D, S, hd = 128, 128, 32
    conv = 4 * D * D
    full = D * hd * (2 * 4 + 2 * 2)
    experts = D * 16 + 3 * D * 48 * 2 * 4 / 16
    # the head once (the embedding is a gather); a convolution layer's taps
    # and gates as 2 K + 2 FLOPs a channel, no S x S product
    return 6.0 * (3 * conv + 2 * full + 3 * D * 256 + 4 * experts
                  + 1024 * D) + 3.0 * (2 * 4.0 * 4 * hd * S / 2
                                       + 3 * (2 * 3 + 2) * D)


def _olmo_hybrid_flops():
    D, S, H, dk, dv, C = 128, 128, 4, 12, 24, 32
    linear = D * H * (2 * dk + 3 * dv + 2)
    full = 4 * D * D
    scan = 2.0 * H * (C * (1.5 * dk + dv) + 3 * dk * dv)
    # the head once (the embedding is a gather); attention at half the
    # square; the scan's products a chunk of 32
    return 6.0 * (3 * linear + full + 4 * 3 * D * 256 + 1024 * D) \
        + 3.0 * (4.0 * D * S / 2 + 3 * scan)


def _ouro_flops():
    D, S, H, hd, F, V, L, T = 256, 128, 2, 128, 512, 1024, 2, 4
    layer = 4 * D * H * hd + 3 * D * F + 4 * D  # four norm vectors
    # every pass meets the layers, the final norm, the head and the gate
    # (a weight and a bias); the embedding is met once; attention at the
    # whole square, as the program's own count has it
    return 6.0 * (T * (L * layer + D + V * D + D + 1) + V * D) \
        + 12.0 * T * L * H * hd * S


FAMILIES: Dict[str, Family] = {row.name: row for row in (
    Family(
        "nemotron_h", "tiny-nemotron-h", "MEMEM*EME",
        # a router that prefers some experts and a scan whose decays matter
        scale={"experts.router": 20.0, "ssm.in_proj": 5.0},
        stacks=frozenset({"wte", "ssm", "attn", "experts", "final_norm",
                          "lm_head"}),
        # On the CPU over four seeds of uniform rows, S=128: the leaves'
        # median error read 0.0120-0.0124 in the program (largest leaf
        # 0.048-0.107) and 0.102-0.109 in the control (largest leaf
        # 0.41-0.50, over the 0.12 that three times the limit allows).
        control=Control(0.04, seeds=(0, 1), program_ok=True),
        first_call={
            "layer_kinds": "MEMEM*EME", "ssm_heads": 8, "ssm_state": 16,
            "ssm_chunk": 32, "ssm_chunks": 8, "ssm_scan_kernel": False,
            "ssm_scan_grid": None, **_EXPERTS,
            "attn_positions": 128, "loss_positions": 128,
            # the attention kind's own since PR 46, whatever else the
            # pattern holds
            "heads_held": 4, "heads_total": 4, "attn_gate": False,
            # one convolution (xBC) each of the four M layers, XLA's form
            # off the chip (PR 61)
            "conv_kernel": "xla", "conv_calls": 4}),
    Family(
        "solar_open2", "tiny-solar-open2", "*EKEKEKE",
        # a router that prefers some experts, decays and betas that matter
        scale={"experts.router": 20.0, "kda.wq": 5.0, "kda.wk": 5.0,
               "kda.wv": 5.0, "kda.w_fb": 5.0, "kda.w_beta": 5.0},
        stacks=frozenset({"wte", "kda", "attn", "experts", "final_norm",
                          "lm_head"}),
        leaves_among={"attn": frozenset({"wg"}),
                      "experts": frozenset({"w_gate", "shared_gate"})},
        # Over three seeds: the median read 0.027-0.031 in the program
        # (largest leaf 0.12-0.20) and 0.25-0.28 in the control (largest
        # leaf 0.41-0.56, over the 0.24 that three times the limit allows).
        control=Control(0.08, program_ok=True),
        first_call={
            "layer_kinds": "*EKEKEKE", "kda_heads": 2, "kda_head_dim": 16,
            "kda_chunk": 32, "kda_chunks": 8, "kda_scan_kernel": False,
            "kda_scan_grid": None, "heads_held": 2, "heads_total": 8,
            "attn_gate": True, **_EXPERTS, "attn_positions": 128,
            "loss_positions": 128,
            # q, k and v of each of the three K layers (PR 61)
            "conv_kernel": "xla", "conv_calls": 9}),
    Family(
        "joyai_llm_flash", "tiny-joyai", "LDLELE",
        # a router that prefers some experts, a softmax far from uniform
        scale={"experts.router": 8.0, "mla.wq_b": 5.0, "mla.wkv_b": 5.0},
        stacks=frozenset({"wte", "mla", "dense", "experts", "mtp",
                          "final_norm", "lm_head"}),
        leaves={"mtp": frozenset({"embed_norm", "hidden_norm", "w_eh",
                                  "final_norm"})},
        # Over three seeds: the median read 0.008-0.011 in the program and
        # 0.060-0.061 in the control.
        control=Control(0.025, program_ok=True),
        first_call={
            "layer_kinds": "LDLELE", "mla_heads": 2, "mla_qk_head_dim": 24,
            "mla_v_head_dim": 16, "mla_latents": (48, 32),
            "dense_width": 128, "mtp_depth": 1, "mtp_weight": 0.3,
            **_EXPERTS, "attn_positions": 128, "loss_positions": 128,
            # q's and the rotary key's pass of each of the four latent
            # layers (the module's among them), by the product: 24 and 8
            # lanes (PR 53)
            "rope_kernel": False, "rope_calls": 8},
        flops=_joyai_flops()),
    Family(
        "xing4_0", "tiny-xing4", "LDLELE",
        # joyai's, and maps off their start: logits a few units wide, a
        # stream map far from the identity, a read and a write far from
        # uniform
        scale={"experts.router": 8.0, "mla.wq_b": 5.0, "mla.wkv_b": 5.0,
               "hc.alpha": 100.0, "hc.phi": 5.0, "hc.base": 0.1},
        noise=("hc.base",),
        stacks=frozenset({"wte", "mla", "dense", "experts", "mtp", "hc",
                          "final_norm", "lm_head"}),
        leaves={"mtp": frozenset({"embed_norm", "hidden_norm", "w_eh",
                                  "final_norm"}),
                "hc": frozenset({"phi", "alpha", "base"})},
        control=Control(0.025, program_ok=True),
        scopes=("mhc", "mhc/mhc_maps", "mhc/mhc_mix", "attn/latent",
                "moe_held", "shared_expert", "mtp",
                "rematted_computation/mhc/mhc_maps"),
        no_scopes=("ssm", "kda", "gdn", "window"),
        registered=("mhc", "mhc_maps", "mhc_mix"),
        first_call={
            "layer_kinds": "LDLELE", "streams": 4, "hc_sinkhorn_iters": 20,
            # the pattern's six and the module's two
            "mhc_sublayers": 8, "mla_heads": 2, "mla_qk_head_dim": 24,
            "mla_v_head_dim": 16, "mla_latents": (48, 32),
            "dense_width": 128, "mtp_depth": 1, "mtp_weight": 0.3,
            **_EXPERTS, "attn_positions": 128, "loss_positions": 128,
            "rope_kernel": False, "rope_calls": 8},
        flops=_xing4_flops()),
    Family(
        "laguna", "tiny-laguna", "*DWEWEWE*E",
        # a router that prefers some experts, softmaxes far from uniform
        scale={"experts.router": 8.0,
               **{f"{stack}.{leaf}": 5.0 for stack in ("attn", "window")
                  for leaf in ("wq", "wk", "wg")}},
        stacks=frozenset({"wte", "attn", "window", "dense", "experts",
                          "final_norm", "lm_head"}),
        leaves={"attn": _ATTN | {"wg"}, "window": _ATTN | {"wg"}},
        # Over three seeds: the median read 0.009-0.012 in the program and
        # 0.063-0.064 in the control.  At this size an expert's gradient is
        # a sum over a handful of rows, and the held experts' leaves of the
        # program read 0.04-0.19.
        control=Control(0.025, leaves_outside="experts"),
        scopes=("attn/window", "window/attn_kernel", "attn/attn_kernel",
                "shared_expert", "moe_held"),
        registered=("window",),
        first_call={
            "layer_kinds": "*DWEWEWE*E", "attn_gate": "head",
            "rope_rotary_lanes": 8, "rope_yarn_factor": 4.0,
            "attn_window": 16, "window_heads": 6},
        flops=_laguna_flops()),
    Family(
        "lfm2_moe", "tiny-lfm2", "CD*ECE*ECE",
        # a router that prefers some experts, softmaxes far from uniform
        scale={"experts.router": 8.0, "attn.wq": 5.0, "attn.wk": 5.0,
               "shortconv.in_proj": 20.0},
        noise=("attn.q_norm", "attn.k_norm", "shortconv.conv_norm"),
        stacks=frozenset({"wte", "attn", "shortconv", "dense", "experts",
                          "final_norm"}),  # a tied head: no ``lm_head``
        leaves={"shortconv": frozenset({"conv_norm", "in_proj", "conv_w",
                                        "out_proj"}),
                "attn": _ATTN | {"q_norm", "k_norm"}},
        control=Control(0.03),
        scopes=("shortconv", "shortconv/shortconv_gate", "attn_kernel",
                "moe_held"),
        no_scopes=("shared_expert",),
        registered=("shortconv", "shortconv_gate"),
        first_call={
            "layer_kinds": "CD*ECE*ECE", "qk_norm": "head",
            "shortconv_taps": 3, "shortconv_width": 128,
            "shortconv_layers": 3, "dense_width": 256,
            # q and k of each of the two attention layers in one call, by
            # the product: heads of 32 lanes
            "rope_kernel": False, "rope_calls": 2,
            # the gate of each of the three C layers (PR 61)
            "conv_kernel": "xla", "conv_calls": 3},
        flops=_lfm2_flops()),
    Family(
        "olmo_hybrid", "tiny-olmo-hybrid", "GDGDGD*D",
        # Nothing an identity: norm weights off one, decays and betas that
        # differ by position, an embedding of unit scale.  (At the
        # initialisation's 0.02 the first sub-layers' outputs lie under the
        # norms' eps, where a norm is a constant gain and a sub-layer a
        # polynomial in its input: each doubles or triples the relative
        # error that reaches it, and the bf16 program's gradients read 0.13
        # off the float32 ones on every leaf.  A trained residual stream is
        # not there.)
        scale={"gdn.w_a": 10.0, "gdn.w_b": 10.0, "wte": 50.0},
        # bf16 at the initialisation's ``w_a``: a decay is ``exp`` of
        # ``exp(A_log)`` (up to 16) times ``u w_a``, and ``u`` is the bf16
        # residual stream, so at ten times the initialisation one rounding
        # of ``u`` is 8 % of a decay and the leaves read 0.3-0.5 off (a
        # model's property, in any bf16 program: the float32 case holds the
        # same weights to 2e-4)
        scale_bfloat16={"gdn.w_a": 1.0, "gdn.w_b": 1.0},
        noise=("gdn.gdn_norm", "gdn.head_norm", "attn.attn_norm",
               "attn.q_norm", "attn.k_norm", "dense.mlp_norm"),
        stacks=frozenset({"wte", "lm_head", "gdn", "attn", "dense",
                          "final_norm"}),
        leaves={"gdn": frozenset({
                    "gdn_norm", "wq", "wk", "wv", "wg", "wo", "w_a", "w_b",
                    "A_log", "dt_bias", "head_norm", "conv_q", "conv_k",
                    "conv_v"}),
                "attn": _ATTN | {"q_norm", "k_norm"}},
        # At this size and at the initialisation the program reads 0.12-0.14
        # where the pre-norm presets read 0.01 (``scale`` above says why);
        # the control reads 0.98-1.15.
        control=Control(0.35),
        scopes=("gdn", "gdn/gdn_conv", "gdn/gdn_scan", "attn_kernel", "mlp",
                "rematted_computation/gdn/gdn_scan"),
        no_scopes=("router", "moe_dispatch", "shared_expert", "kda"),
        registered=("gdn", "gdn_conv", "gdn_scan"),
        first_call={
            "layer_kinds": "GDGDGD*D", "qk_norm": True, "gdn_heads": 4,
            "gdn_key_dim": 12, "gdn_value_dim": 24, "gdn_chunk": 32,
            "gdn_chunks": 2 * 128 // 32,
            "gdn_scan_kernel": False, "gdn_scan_grid": None,  # heads of 12
            "dense_width": 256, "attn_gate": False,
            "remat_routing_bytes": 0,  # no layer routes
            # q, k and v of each of the three G layers (PR 61)
            "conv_kernel": "xla", "conv_calls": 9},
        flops=_olmo_hybrid_flops()),
    Family(
        "olmoe", "tiny-olmoe", None,
        # a router that prefers some experts, so the load is uneven
        scale={"blocks.router": 20.0},
        stacks=frozenset({"wte", "blocks", "final_norm", "lm_head"}),
        leaves=_BLOCKS, f32_tol=(F32_LOSS_TOL, 1e-4), q_block=SEQ_LEN,
        first_call={"experts_held": 8, "experts_total": 8, "block_length": 0,
                    "attn_positions": 128, "loss_positions": 128}),
    Family(
        "ouro", "tiny-ouro", None,
        # nothing an identity: the four norms off ones, a gate that differs
        # by position and leans to one side (weight and bias, one leaf,
        # moved off their start and brought to a tenth: logits of +-2)
        scale={"exit_gate": 0.5},
        noise=("blocks.attn_norm", "blocks.attn_norm_2", "blocks.mlp_norm",
               "blocks.mlp_norm_2", "exit_gate"),
        stacks=frozenset({"wte", "blocks", "final_norm", "lm_head",
                          "exit_gate"}),
        leaves={"blocks": _ATTN | {"attn_norm_2", "mlp_norm", "mlp_norm_2",
                                   "w_gate", "w_up", "w_down"}},
        # On the CPU over four seeds of uniform rows, S=128: the leaves'
        # median error read 0.0120-0.0142 in the program (largest leaf
        # 0.0136-0.0150) and 0.125-0.141 in the control (largest leaf
        # 0.147-0.155, over the 0.12 that three times the limit allows).
        control=Control(0.04, seeds=(0, 1), program_ok=True),
        # (a scanned stack's paths are cut at the loops' bodies in the
        # lowered text: the compiled step's whole paths are rows of
        # tests/data/v5e_step_op_names.json)
        scopes=("exit_gate", "lm_head", "attn", "attn_kernel", "mlp"),
        no_scopes=("router", "moe_dispatch", "noise", "mtp"),
        registered=("exit_gate",),
        first_call={"ut_steps": 4, "experts_held": 0, "experts_total": 0,
                    "block_length": 0, "attn_positions": 128,
                    "loss_positions": 128,
                    # q and k of the one scanned layer, by the kernel's
                    # shape but off the chip: the product
                    "rope_kernel": False, "rope_calls": 1},
        flops=_ouro_flops()),
    Family(
        "sdar", "tiny-sdar", None,
        # a router that prefers some experts, so the held share is uneven
        scale={"blocks.router": 20.0},
        stacks=frozenset({"wte", "blocks", "final_norm", "lm_head"}),
        leaves=_BLOCKS, f32_tol=(F32_LOSS_TOL, 1e-4), q_block=SEQ_LEN,
        # a row is its noised copy and then itself
        first_call={"experts_held": 2, "experts_total": 8, "block_length": 4,
                    "attn_positions": 256, "loss_positions": 128}),
)}

#: the rows of ``models/hybrid.py``, and for each letter of its ``KINDS``
#: the first row whose pattern holds it
HYBRID = tuple(name for name, row in FAMILIES.items() if row.pattern)
HOLDER = {kind: next(name for name in HYBRID
                     if kind in FAMILIES[name].pattern)
          for kind in "MKE*LDWCG"}


# ------------------------------------------------------------- the helpers
def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def l2_err(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def mesh(**axes):
    """A mesh of the first CPU devices with these axes' sizes."""
    from ray_tpu.parallel import MeshSpec, make_mesh

    return make_mesh(MeshSpec(**axes), jax.devices()[:MeshSpec(**axes).size])


def named(rungs) -> Dict[str, int]:
    """What ``hybrid._layer_sizes``' rungs name: a chip's bytes of each name
    over all the layers that name it."""
    sizes: Dict[str, int] = {}
    for rung in rungs:
        sizes[rung.name] = sizes.get(rung.name, 0) + rung.nbytes * rung.layers
    return sizes


def out_and_grads(fn, args, dy):
    """(``fn(*args)``, its pull-back of ``dy``), jitted as one program."""
    def run(*args):
        out, vjp = jax.vjp(fn, *args)
        return out, vjp(dy)
    return jax.jit(run)(*args)


def rows(vocab: int, n: int = 2, seed: int = 0):
    """(tokens, targets) of ``n`` rows of uniform ids."""
    ids = np.random.default_rng(seed).integers(
        0, vocab, (n, SEQ_LEN + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def family(name: str, dtype="bfloat16", options: Optional[Dict] = None,
           **changes):
    """(the rehearsal file with ``changes`` to its keys, what the family's
    adapter builds from it): attention on the einsum path, operands,
    residual stream and logits in ``dtype``."""
    row = FAMILIES[name]
    config = dict(spec.load_json(spec.BENCH_DIR, "configs",
                                 row.preset + ".json"), **changes)
    config["options"] = {"attn_impl": "xla", "dtype": jnp.dtype(dtype),
                         "logits_dtype": jnp.dtype(dtype), **(options or {})}
    return config, spec.load_module("models", name).build(config, SEQ_LEN)


def from_file(preset: str):
    """What its family's adapter builds from a rehearsal file as it is."""
    config = spec.load_json(spec.BENCH_DIR, "configs", preset + ".json")
    return spec.load_module("models", config["family"]).build(config, SEQ_LEN)


@functools.cache
def _model(name: str):
    # every adapter hands the step the model configuration it built
    return from_file(FAMILIES[name].preset).make_train_step.args[0]


def preset(name: str, **replace):
    """The model configuration the family's adapter builds from its
    rehearsal file (a ``HybridConfig`` or a ``LlamaConfig``), with fields
    replaced: ``preset("laguna", attn_impl="xla")``."""
    return dataclasses.replace(_model(name), **replace)


def float32(name: str, **replace):
    """:func:`preset` in float32 on the einsum path."""
    return preset(name, attn_impl="xla", dtype=jnp.float32,
                  logits_dtype=jnp.float32, **replace)


def shaken(name: str, params, dtype="float32", seed: int = 7):
    """``params`` with the row's leaves scaled and moved (those of them
    that ``params`` holds: a test may hand one kind's stack alone)."""
    row = FAMILIES[name]
    out = {stack: dict(leaves) if isinstance(leaves, dict) else leaves
           for stack, leaves in params.items()}
    key = jax.random.key(seed)
    for path in row.noise:
        stack, _, leaf = path.partition(".")
        if stack in out:
            key, k = jax.random.split(key)
            held = out[stack] if leaf else out
            at = leaf or stack
            held[at] = held[at] + 0.2 * jax.random.normal(k, held[at].shape)
    scale = dict(row.scale)
    if jnp.dtype(dtype) == jnp.bfloat16:
        scale.update(row.scale_bfloat16)
    for path, factor in scale.items():
        stack, _, leaf = path.partition(".")
        if stack not in out:
            continue
        if leaf:
            out[stack][leaf] = out[stack][leaf] * factor
        else:
            out[stack] = out[stack] * factor
    return out


# -------------------------------------- what a row's cases build only once
@functools.cache
def built(name: str, dtype: str = "bfloat16"):
    """:func:`family` of the file as it is."""
    return family(name, dtype)


@functools.cache
def drawn(name: str):
    """The parameters the family's ``init_fn`` draws at key 0 (float32,
    whatever the program's dtype).  Read, never written: :func:`shaken`
    copies."""
    return jax.jit(built(name)[1].init_fn)(jax.random.key(0))


@functools.cache
def program(name: str, dtype: str):
    """Loss and gradients of the program, jitted."""
    return jax.jit(jax.value_and_grad(built(name, dtype)[1].loss_fn))


@functools.cache
def reference(name: str, dtype: str):
    """Loss and gradients of the plain reference, jitted."""
    loss = built(name, dtype)[1].reference_loss
    q_block = FAMILIES[name].q_block
    return jax.jit(jax.value_and_grad(
        lambda p, t, y: loss(p, t, y, q_block)))


@functools.cache
def compared(name: str, dtype: str) -> Dict[str, Any]:
    """The program and the reference on the same shaken parameters and the
    same two rows: ``params``, ``tokens``, ``targets``, ``loss``, ``grads``,
    ``ref_loss``, ``ref_grads``."""
    params = shaken(name, drawn(name), dtype)
    tokens, targets = rows(built(name, dtype)[1].vocab_size)
    loss, grads = program(name, dtype)(params, tokens, targets)
    ref_loss, ref_grads = reference(name, dtype)(params, tokens, targets)
    return dict(params=params, tokens=tokens, targets=targets, loss=loss,
                grads=grads, ref_loss=ref_loss, ref_grads=ref_grads)
