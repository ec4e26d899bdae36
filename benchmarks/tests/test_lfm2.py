"""Family ``lfm2_moe``: the configuration against the catalog's row, the
parameter count, the cost file against the compiled shapes and by hand, the
new readers on a made-up record, ``attention_calls``' pairs, the reference
against the program at the rehearsal preset, and the cell's rehearsal.  (The
convolution kind, the tied head and the share of the experts against their
written-out formulas are tier 1's ``tests/test_lfm2.py``.)"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import correct, cost_lfm2, spec
from benchmarks.lib.peaks import PEAKS
from benchmarks.tests.test_attention_calls import allowed_by_the_program
from benchmarks.tests.test_run import result_line, run

CELL = "lfm2-ep4-s8192"
CONFIG = "lfm2-8b-a1b-l7-ep4"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"step.shortconv_ms", "step.shortconv_gate_ms",
               "step.shortconv_gate_roofline"}
SHARED_METRICS = {"kernels.gmm_held_ms", "kernels.gmm_held_roofline",
                  "step.moe_held_rows", "step.moe_load_max",
                  "step.moe_moved_rows", "step.moe_held_ms",
                  "step.done_period_ms", "step.done_period_spread",
                  "trainer.starved_dispatches"}
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}
S, TOKENS = 8192, 16384
PEAKS_V5E = PEAKS["TPU v5 lite"]


@pytest.fixture(scope="module")
def config():
    return spec.load_json(spec.BENCH_DIR, "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def family(config):
    return spec.load_module("models", "lfm2_moe").build(config, S)


def _tiny_family(dtype="bfloat16"):
    tiny = spec.load_json(spec.BENCH_DIR, "configs", "tiny-lfm2.json")
    tiny["options"] = {"attn_impl": "xla", "dtype": jnp.dtype(dtype),
                       "logits_dtype": jnp.dtype(dtype)}
    return tiny, spec.load_module("models", "lfm2_moe").build(tiny, 128)


def test_only_the_stated_keys_differ_from_the_source(config):
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    assert config["source"] == row["source_url"]
    published = row["config"]
    differ = {k for k, v in published.items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == REDUCED
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert set(entry["reduced"]) == REDUCED
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    # no width among the reduced keys, and every published width
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in REDUCED)
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
        "conv_L_cache", "num_dense_layers")] \
        == [2048, 32, 8, 7168, 1792, 4, 3, 2]
    assert config["layer_types"] == published["layer_types"] \
        and len(config["layer_types"]) == 24
    assert config["num_hidden_layers"] == 7
    assert config["num_experts_published"] == published["num_experts"] == 32
    assert config["experts_held"] == [0, 8]
    assert config["vocab_size"] * 4 == published["vocab_size"] \
        == config["vocab_size_published"]
    assert config["eos_token_id"] < config["vocab_size"]
    assert {"equations", "tie_word_embeddings", "qk_norm",
            "expert_equations", "router_bias", "initialisation", "init_seed",
            "lr_warmup_steps", "eos_token_id", "training_dtype"} \
        <= set(config["assumed"])
    assert "1e-6" in config["assumed"]["expert_equations"]
    assert config["lr_warmup_steps"] == 2000 and config["init_seed"] == 56
    assert (config["router_bias_seed"], config["router_bias_std"]) \
        == (56, 0.02)
    assert "four chips" in config["stands_for"]
    assert config["check"]["seed_grad_tol"] and config["check_why"]
    module = spec.load_module("models", "lfm2_moe")
    assert module.layers_run(config) == [0, 2, 3, 4, 5, 6, 7]
    assert module.pattern(config) == "CD*ECECECE*ECE"
    # at the published depth every layer runs, the two dense ones first
    whole = dict(config, num_hidden_layers=24)
    assert module.layers_run(whole) == list(range(24))
    assert module.pattern(whole).count("D") == 2 \
        and module.pattern(whole).count("*") == 6


def test_parameters_are_the_issues_table(config, family):
    shapes = jax.eval_shape(family.init_fn, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048 + 2048
    full = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2048 + 2 * 64
    dense = 3 * 2048 * 7168 + 2048
    experts = 8 * 3 * 2048 * 1792 + 2048 * 32 + 2048
    embed = 16384 * 2048
    # ISSUE 56's rows: 33.55 M + 60.83 M + 4 x 104.93 M + 2 x 98.64 M
    assert [round(x / 1e6, 2) for x in (
        embed, conv + dense, conv + experts, full + experts)] \
        == [33.55, 60.83, 104.93, 98.64]
    assert n == 5 * conv + 2 * full + dense + 6 * experts + embed + 2048 \
        == cost_lfm2.params_held(config)
    assert round(n / 1e6, 1) == 711.4
    assert n * 14 / 2 ** 30 == pytest.approx(9.28, abs=0.01)  # GiB of state
    assert "lm_head" not in shapes
    assert shapes["shortconv"]["in_proj"].shape == (5, 2048, 6144)
    assert shapes["shortconv"]["conv_w"].shape == (5, 3, 2048)
    assert shapes["attn"]["wq"].shape == (2, 2048, 32 * 64)
    assert shapes["attn"]["wk"].shape == (2, 2048, 8 * 64)
    assert shapes["attn"]["q_norm"].shape == (2, 64)
    assert shapes["dense"]["w_gate"].shape == (1, 2048, 7168)
    assert shapes["experts"]["router"].shape == (6, 2048, 32)
    assert shapes["experts"]["w_gate"].shape == (6, 8, 2048, 1792)
    assert "shared_up" not in shapes["experts"]
    assert family.vocab_size == 16384


def test_model_flops_are_the_programs_and_count_taps_not_a_product(config,
                                                                   family):
    from ray_tpu.models import hybrid

    _, model = spec.load_module("models", "lfm2_moe").model_config(config, S)
    assert family.flops_per_token == hybrid.flops_per_token(model)
    matmuls = 5 * 4 * 2048 * 2048 + 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) \
        + 3 * 2048 * 7168 + 6 * (2048 * 32 + 4 * 8 / 32 * 3 * 2048 * 1792) \
        + 16384 * 2048
    taps = 5 * (2 * 3 + 2) * 2048  # 8 FLOPs a channel a layer, forward
    assert family.flops_per_token == 6.0 * matmuls + 6.0 * 2 * S * 2048 \
        + 3.0 * taps
    assert family.flops_per_token / 1e9 == pytest.approx(1.695, abs=0.001)
    # the held products are 27 % of the matrix entries a token meets
    held = 6 * 4 * 8 / 32 * 3 * 2048 * 1792
    assert (round(matmuls / 1e6), round(100 * held / matmuls)) == (249, 27)


def test_the_gate_passs_bytes_are_the_compiled_steps_shapes(config):
    """``cost_lfm2.gate_step_cost`` from the shapes the program's own pass
    has at the cell's size: ``[B | C | u]`` in and the result out forward,
    those and the cotangent in and ``[B | C | u]``'s out backward (the taps
    beside them are noise), traced here without a device."""
    from ray_tpu.models import shortconv

    bcu = jax.ShapeDtypeStruct((2, S, 3 * 2048), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((3, 2048), jnp.float32)
    out = jax.eval_shape(shortconv.gated_conv, bcu, w)
    assert out.shape == (2, S, 2048) and out.dtype == jnp.bfloat16

    def nbytes(*arrays):
        return sum(a.size * a.dtype.itemsize for a in arrays)

    _, pull = jax.eval_shape(lambda b, w: jax.vjp(shortconv.gated_conv, b, w),
                             bcu, w)
    d_bcu, _ = jax.eval_shape(
        lambda b, w, g: jax.vjp(shortconv.gated_conv, b, w)[1](g), bcu, w,
        out)
    assert d_bcu.shape == bcu.shape and d_bcu.dtype == bcu.dtype
    forward = nbytes(bcu, out)
    backward = nbytes(bcu, out, d_bcu)
    layers = 5
    for recomputed, want in ((False, forward + backward),
                             (True, 2 * forward + backward)):
        flops, moved = cost_lfm2.gate_step_cost(config, TOKENS, recomputed)
        assert moved == layers * want
        assert flops == layers * TOKENS * (3 + recomputed) * 8 * 2048
    # a position: 3 + 1 values a channel forward, 4 + 3 backward
    assert forward == TOKENS * 4 * 2048 * 2
    assert backward == TOKENS * 7 * 2048 * 2
    seconds, bound = cost_lfm2.gate_least_time(
        config, TOKENS, True, PEAKS_V5E.flops, PEAKS_V5E.hbm_bw)
    assert bound == "memory"
    assert 1e3 * seconds == pytest.approx(6.14, abs=0.01)
    assert 1e3 * cost_lfm2.gate_least_time(
        config, TOKENS, False, PEAKS_V5E.flops, PEAKS_V5E.hbm_bw)[0] \
        == pytest.approx(4.51, abs=0.01)


def _made_up(config, table):
    """A record whose anatomy table is ``table`` (ms a step by
    ``phase/part``), as the three new readers see one."""
    run = types.SimpleNamespace(
        cell={"config_file": config}, peaks=PEAKS_V5E, chips=1,
        tokens_per_step=TOKENS, seq_len=S)
    run.anatomy = {name: tuple(name.split("/")) for name in table}
    run.self_seconds = {name: ms / 1e3 for name, ms in table.items()}
    run.steady = (0.0, 1.0, 1, [1.0])
    run.trace = types.SimpleNamespace(first=types.SimpleNamespace(ops=[]))
    return run


def test_the_new_readers_on_a_made_up_record(config):
    readers = {name: spec.load_module("layer_metrics", name)
               for name in NEW_METRICS}
    table = {"forward/shortconv": 20.0, "backward/shortconv": 40.0,
             "forward/shortconv_gate": 3.0, "backward/shortconv_gate": 9.0,
             "forward/attn": 7.0}
    made_up = _made_up(config, table)
    assert readers["step.shortconv_ms"].read(made_up) \
        == pytest.approx(72.0)
    assert readers["step.shortconv_gate_ms"].read(made_up) \
        == pytest.approx(12.0)
    roofline = readers["step.shortconv_gate_roofline"]
    assert roofline.read(made_up) == pytest.approx(100 * 4.506 / 12.0,
                                                   rel=1e-3)
    assert roofline.describe(made_up)["recomputed"] is False
    again = _made_up(config, dict(table, **{"recompute/shortconv_gate": 3.0}))
    assert roofline.read(again) == pytest.approx(100 * 6.144 / 15.0, rel=1e-3)
    assert roofline.describe(again) == {
        "least_ms": pytest.approx(6.144, rel=1e-3), "bound_by": "memory",
        "recomputed": True}
    # a program without the scopes (the parent of PR 56): nothing, no raise
    bare = _made_up(config, {"forward/attn": 7.0, "backward/mlp": 9.0})
    for reader in readers.values():
        assert reader.read(bare) is None
        assert not reader.describe(bare)
    nothing = types.SimpleNamespace(anatomy=None, trace=None, steady=None,
                                    peaks=PEAKS_V5E, cell={})
    assert all(reader.read(nothing) is None for reader in readers.values())


def test_attention_calls_is_one_causal_kind_at_the_layers_heads(family):
    (call,) = family.attention_calls
    assert (call.name, call.q_heads, call.kv_heads, call.qk_dim,
            call.v_dim) == ("causal", 32, 8, 64, 64)
    assert call.pairs(S) == S * S / 2
    assert call.shapes(S) == ((32, S, 64), (8, S, 64), (8, S, 64))
    # the tiny preset's pairs are the pairs the program's mask allows
    tiny = spec.load_json(spec.BENCH_DIR, "configs", "tiny-lfm2.json")
    (small,) = spec.load_module("models", "lfm2_moe").build(
        tiny, 64).attention_calls
    allowed = allowed_by_the_program(tiny, 64)
    assert np.array_equal(allowed, np.tril(np.ones((64, 64), bool)))
    assert allowed.sum() - small.pairs(64) == 64 / 2


# ------------------------------------------------- program against reference
@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    ("float32", 1e-5, 2e-4), ("bfloat16", correct.LOSS_TOL,
                              correct.GRAD_TOL)], ids=["float32", "bfloat16"])
def test_the_program_matches_the_reference_at_the_rehearsal_preset(
        dtype, loss_tol, grad_tol):
    _, family = _tiny_family(dtype)
    params = jax.jit(family.init_fn)(jax.random.key(0))
    rows = np.random.default_rng(1).integers(
        0, family.vocab_size, (2, 129)).astype(np.int32)
    tokens, targets = rows[:, :-1], rows[:, 1:]
    loss, grads = jax.jit(jax.value_and_grad(family.loss_fn))(
        params, tokens, targets)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p, t, y: family.reference_loss(p, t, y, 64)))(
        params, tokens, targets)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < loss_tol
    for path, (a, b) in jax.tree_util.tree_flatten_with_path(
            jax.tree.map(lambda a, b: (a, b), grads, ref_grads),
            is_leaf=lambda x: isinstance(x, tuple))[0]:
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                    / jnp.max(jnp.abs(b)))
        assert err < grad_tol, (jax.tree_util.keystr(path), err)


def test_the_reference_imports_nothing_from_the_program():
    source = open(os.path.join(spec.BENCH_DIR, "reference",
                               "lfm2_moe.py")).read()
    assert "ray_tpu" not in source.split('"""', 2)[2]


def test_the_expert_parts_are_read_where_no_shared_expert_stands(config):
    """The cell's expert layers have no ``shared_expert`` scope, so
    ``step.moe_routed_ms`` (which reads ``router`` + ``moe_dispatch`` +
    ``moe_held`` beside one) finds nothing and ``step.moe_held_ms`` reads
    the same three parts: the cell is on the second list and not the first
    (a traced line that lacks a listed metric is refused)."""
    table = {"forward/router": 1.5, "forward/moe_dispatch": 20.0,
             "backward/moe_held": 48.0, "forward/shortconv": 20.0}
    made_up = _made_up(config, table)
    lists = {m["name"]: m["workloads"]
             for m in spec.load_benchmark()["per_layer"] if "workloads" in m}
    held = spec.load_module("layer_metrics", "step.moe_held_ms")
    routed = spec.load_module("layer_metrics", "step.moe_routed_ms")
    assert held.read(made_up) == pytest.approx(69.5)
    assert routed.read(made_up) is None
    assert CELL in lists["step.moe_held_ms"]
    assert CELL not in lists["step.moe_routed_ms"] \
        and CELL not in lists["step.moe_shared_ms"]


def test_the_cell_rehearses_with_every_new_metric():
    """``--rehearse --trace 1`` on the CPU: ``correct`` true, and every new
    per-layer metric's reader runs: the times and the share (which a CPU run
    never prints) are read from a trace that has no device plane and come
    back None without raising; the counts the cell shares with the other
    expert cells are printed."""
    bench = spec.load_benchmark()
    cell = spec.load_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "epochs8-s8192-b2", CONFIG)
    traffic, older = cell["traffic_file"], spec.load_json(
        spec.BENCH_DIR, "traffic", "epochs8-s8192-b1.json")
    assert {k for k in older if traffic[k] != older[k]} \
        == {"seqs_per_chip", "data_seed", "why"}
    assert (traffic["seqs_per_chip"], traffic["data_seed"]) == (2, 56)
    assert {m["name"] for m in cell["metrics"]["per_layer"]
            if m.get("workloads") == [CELL]} == NEW_METRICS
    assert SHARED_METRICS == {m["name"] for m in cell["metrics"]["per_layer"]
                              if CELL in m.get("workloads", ())
                              and m["workloads"] != [CELL]}
    line = result_line(run(spec.ROOT, "--workload", CELL, "--seed",
                           "3987654321", "--seconds", "1", "--trace", "1",
                           "--rehearse"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["step.compiles_in_window"]["value"] == 0
    assert {"step.moe_held_rows", "step.moe_moved_rows"} \
        <= set(line["metrics"])
    assert not NEW_METRICS & set(line["metrics"])
    for name in NEW_METRICS:
        reader = spec.load_module("layer_metrics", name)
        assert (reader.UNIT, reader.SOURCE, reader.MOVES, reader.LAYER) \
            == next((m["unit"], m["source"], m["moves"], m["layer"])
                    for m in bench["per_layer"] if m["name"] == name)


def test_the_adapter_stops_at_once_where_the_kind_is_missing(monkeypatch,
                                                             config):
    """On a checkout whose ``hybrid.KINDS`` has no ``C`` (the parent of PR
    56) the family says so and exits: no hang, no traceback."""
    from ray_tpu.models import hybrid

    monkeypatch.setattr(hybrid, "KINDS", {
        k: v for k, v in hybrid.KINDS.items() if k != "C"})
    with pytest.raises(SystemExit, match="no gated short-convolution"):
        spec.load_module("models", "lfm2_moe").build(config, S)


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("use_expert_bias", False),
    ("tie_word_embeddings", False)])
def test_the_adapter_refuses_what_the_program_does_not_implement(
        config, key, value):
    with pytest.raises(SystemExit, match=key):
        spec.load_module("models", "lfm2_moe").build(
            dict(config, **{key: value}), S)
