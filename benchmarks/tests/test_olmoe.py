"""The OLMoE family (PR 26): its plain reference against the program at the
tiny preset through the comparison the chip run uses, ``lib/cost_moe.py``
against hand-counted numbers, and the four expert-layer readers on a trace
recorded on the chip (``testdata/tiny-olmoe-named.*``: ``run.py --workload
olmoe-s4096 --rehearse --trace 1 --keep-trace`` on a TPU v5e, the step's
``anatomy()`` and Mosaic calls asked of the same process afterwards)."""

import json
import os

import jax
import numpy as np
import pytest

from benchmarks.lib import anatomy, correct, cost_moe, spec, trace_reduce as tr
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.record import RunRecord
from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh

OLMOE = spec.load_json(spec.BENCH_DIR, "configs", "olmoe-1b-7b-l1.json")
TINY = spec.load_json(spec.BENCH_DIR, "configs", "tiny-olmoe.json")


def test_reference_agrees_with_the_program():
    family = spec.load_module("models", "olmoe").build(TINY, 256)
    params = jax.jit(family.init_fn)(jax.random.key(0))
    mesh = make_mesh(MeshSpec(), jax.local_devices()[:1])
    rows = np.random.default_rng(0).integers(
        0, family.vocab_size, (1, 257)).astype(np.int32)
    tokens, targets = (jax.device_put(a, batch_sharding(mesh))
                       for a in (rows[:, :-1], rows[:, 1:]))
    got = correct.compare(family, params, tokens, targets, mesh)
    assert got["ok"], got
    assert set(got["grad_err_by_leaf"]) >= {
        "['blocks']['router']", "['blocks']['w_gate']",
        "['blocks']['q_norm']"}
    assert len(got["grad_err_by_leaf"]) == len(jax.tree.leaves(params))
    assert got["grad_err_max"] > 1e-4  # bf16 against float32, not a copy


def test_the_configuration_keeps_the_published_model():
    published = {"hidden_size": 2048, "intermediate_size": 1024,
                 "num_attention_heads": 16, "num_key_value_heads": 16,
                 "num_experts": 64, "num_experts_per_tok": 8,
                 "norm_topk_prob": False, "vocab_size": 50304,
                 "max_position_embeddings": 4096, "rms_norm_eps": 1e-5,
                 "rope_theta": 10000, "tie_word_embeddings": False,
                 "hidden_act": "silu", "attention_bias": False,
                 "clip_qkv": None, "rope_scaling": None,
                 "model_type": "olmoe"}
    assert {k: OLMOE[k] for k in published} == published
    assert OLMOE["num_hidden_layers"] == 1
    assert list(OLMOE["reduced"]) == ["num_hidden_layers"]
    family = spec.load_module("models", "olmoe").build(OLMOE, 4096)
    shapes = jax.eval_shape(family.init_fn, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 625_616_896
    assert shapes["blocks"]["w_gate"].shape == (1, 64, 2048, 1024)


def test_cost_against_hand_counted_flops():
    # a token meets: four 2048 x 2048 projections, the 2048 x 64 router, 8
    # experts of 3 x 2048 x 1024, the 50304 x 2048 head
    assert cost_moe.olmoe_matmul_params(OLMOE) \
        == 16_777_216 + 131_072 + 50_331_648 + 103_022_592
    # x 6, plus causal attention 6 x 1 x 4096 x 2048: 1071.9 MFLOP a token,
    # 57.7 % of it the head's
    flops = cost_moe.model_flops_per_token(OLMOE, 4096)
    assert flops == 6 * 170_262_528 + 50_331_648
    assert 6 * 103_022_592 / flops == pytest.approx(0.577, abs=1e-3)
    full = dict(OLMOE, num_hidden_layers=16)
    assert 6 * 103_022_592 / cost_moe.model_flops_per_token(full, 4096) \
        == pytest.approx(0.078, abs=1e-3)
    # one grouped product at the cell's shapes: 65536 rows, 64 groups
    f, b = cost_moe.grouped_product_cost(65536, 64, 2048, 1024)
    assert f == 2 * 65536 * 2048 * 1024 == 274_877_906_944
    assert b == 2 * (64 * 2048 * 1024 + 65536 * 2048 + 65536 * 1024)
    # a layer: nine of them, 18 x rows x hidden x width
    assert cost_moe.expert_layer_cost(OLMOE, 8192) == (9 * f, 9 * b)
    assert cost_moe.expert_layer_cost(full, 8192)[0] == 16 * 9 * f


# ------------------------------------------------- readers, recorded trace
RECORDED = os.path.join(spec.BENCH_DIR, "testdata", "tiny-olmoe-named")


@pytest.fixture(scope="module")
def chip_run():
    trace = tr.load(RECORDED + ".xplane.pb.gz")
    with open(RECORDED + ".anatomy.json") as f:
        held = json.load(f)
    run = RunRecord(
        cell={"config_file": TINY}, chips=1, peaks=peaks_for("TPU v5 lite"),
        tokens_per_step=512, flops_per_step=1.0, seq_len=256,
        attention_calls=(), trace=trace,
        steady=tr.steady_window(trace.first.modules,
                                tr.step_module(trace.first.modules)),
        hlo={"mosaic": held["mosaic"], "collectives": {}})
    run.anatomy = {k: tuple(v) for k, v in held["anatomy"].items()}
    return run


def read(metric, run):
    return spec.load_module("layer_metrics", metric).read(run)


def test_expert_layer_readers_on_a_recorded_chip_trace(chip_run):
    run = chip_run
    moe, dispatch = read("step.moe_ms", run), read("step.moe_dispatch_ms", run)
    kernels = read("kernels.experts_ms", run)
    assert 0 < dispatch < moe
    # the Mosaic calls are the experts part's, and not all of it (the
    # activation and the transposes are there too)
    assert 0 < kernels <= anatomy.part_ms(run, "experts")
    assert moe == pytest.approx(dispatch + anatomy.part_ms(run, "experts"))
    # the scanned layer's 3 forward, 3 recomputed, 3 dx and 3 dW calls, each
    # run once a layer: two layers
    events = run.kernel_events("gmm")
    names = {e.name for e in events}
    assert len(names) == 12 and sum("tgmm" in n for n in names) == 3
    assert len(events) == 12 * 2 * run.steady[2]
    # nested scopes: the expert layer's time is not also mlp's
    parts = sum(anatomy.part_ms(run, p) for p in
                ("router", "moe_dispatch", "experts", "mlp"))
    assert parts < 1e3 * tr.median(run.steady[3])
    roofline = read("kernels.experts_roofline", run)
    assert 0 < roofline < 100
    note = spec.load_module("layer_metrics", "step.moe_ms").describe(run)
    assert sum(note.values()) == pytest.approx(moe)
    assert {k.split("/")[1] for k in note} == {"router", "moe_dispatch",
                                               "experts"}


def test_readers_return_nothing_without_their_sources():
    """A program without the expert layer (or an untraced run) has nothing
    for these readers: they return None and raise nothing."""
    run = RunRecord(cell={"config_file": {}}, chips=1, peaks=None,
                    tokens_per_step=1, flops_per_step=1.0, seq_len=1,
                    attention_calls=())
    run.anatomy = None
    for metric in ("step.moe_ms", "step.moe_dispatch_ms",
                   "kernels.experts_ms", "kernels.experts_roofline"):
        assert read(metric, run) is None
