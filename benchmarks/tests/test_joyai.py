"""Family ``joyai_llm_flash``: the configuration against the catalog's row,
the parameter count, the cost functions' arithmetic, the reference against
the program at the rehearsal preset, the 8-bit control, and the cell's
rehearsal.  (The mixer, the rotary pass and the prediction module against
their written-out formulas are tier 1's ``tests/test_joyai.py``.)"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import correct, cost, cost_joyai, spec
from benchmarks.lib.family import causal
from benchmarks.tests.test_run import result_line, run
from ray_tpu.parallel import MeshSpec, make_mesh

CELL = "joyai-ep16-s8192"
CONFIG = "joyai-llm-flash-l6-ep16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"step.latent_ms", "step.mtp_ms"}
SHARED_METRICS = {"step.moe_held_rows", "step.moe_load_max",
                  "step.moe_moved_rows", "step.moe_shared_ms",
                  "step.moe_routed_ms", "kernels.gmm_held_ms",
                  "kernels.gmm_held_roofline"}
REDUCED = {"num_hidden_layers", "n_routed_experts", "vocab_size"}


@pytest.fixture(scope="module")
def config():
    return spec.load_json(spec.BENCH_DIR, "configs", CONFIG + ".json")


def _tiny_family(dtype="bfloat16"):
    tiny = spec.load_json(spec.BENCH_DIR, "configs", "tiny-joyai.json")
    tiny["options"] = {"attn_impl": "xla", "dtype": jnp.dtype(dtype),
                       "logits_dtype": jnp.dtype(dtype)}
    return tiny, spec.load_module("models", "joyai_llm_flash").build(tiny,
                                                                     128)


def test_only_the_stated_keys_differ_from_the_source(config):
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "JoyAI-LLM-Flash")
    assert config["source"] == row["source_url"]
    published = row["config"]
    differ = {k for k, v in published.items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == REDUCED
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert set(entry["reduced"]) == REDUCED
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    # every published width
    assert [config[k] for k in (
        "hidden_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "intermediate_size",
        "moe_intermediate_size", "num_experts_per_tok",
        "num_nextn_predict_layers", "num_attention_heads")] \
        == [2048, 1536, 512, 128, 64, 128, 7168, 768, 8, 1, 32]
    assert config["num_hidden_layers"] == 6
    assert config["n_routed_experts_published"] \
        == published["n_routed_experts"] == 256
    assert config["experts_held"] == [0, 16]
    assert config["vocab_size"] * 8 == published["vocab_size"] \
        == config["vocab_size_published"]
    assert config["eos_token_id"] < config["vocab_size"]
    assert {"equations", "rope_pairing", "prediction_module", "router_bias",
            "initialisation", "init_seed", "lr_warmup_steps",
            "training_dtype"} <= set(config["assumed"])
    assert config["lr_warmup_steps"] == 2000 and config["init_seed"] == 47
    assert config["mtp_loss_weight"] == 0.3
    assert "sixteen chips" in config["stands_for"]
    assert config["check"]["seed_grad_tol"] and config["check_why"]
    assert spec.load_module("models", "joyai_llm_flash").pattern(config) \
        == "LD" + "LE" * 5


def test_parameters_are_the_issues_arithmetic(config):
    family = spec.load_module("models", "joyai_llm_flash").build(config, 8192)
    shapes = jax.eval_shape(family.init_fn, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    latent = 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 \
        + 4096 * 2048
    expert = 3 * 2048 * 768
    experts = 2048 * 256 + expert + 16 * expert
    dense = 3 * 2048 * 7168
    assert (latent, expert, experts, dense) == (
        26_345_472, 4_718_592, 80_740_352, 44_040_192)
    norms = 7 * (2048 + 1536 + 512) + 7 * 2048 + 2048 + 3 * 2048
    assert n == 7 * latent + dense + 6 * experts + 2 * 16160 * 2048 \
        + 2 * 2048 * 2048 + norms == cost_joyai.params_held(config)
    assert round(n / 1e6, 1) == 787.5                       # ISSUE 47's count
    assert n * 14 / 2 ** 30 == pytest.approx(10.27, abs=0.01)  # GiB of state
    assert shapes["mla"]["wq_b"].shape == (7, 1536, 32 * 192)
    assert shapes["mla"]["wkv_a"].shape == (7, 2048, 512 + 64)
    assert shapes["mla"]["wkv_b"].shape == (7, 512, 32 * 256)
    assert shapes["mla"]["wo"].shape == (7, 32 * 128, 2048)
    assert shapes["dense"]["w_gate"].shape == (1, 2048, 7168)
    assert shapes["experts"]["router"].shape == (6, 2048, 256)
    assert shapes["experts"]["w_gate"].shape == (6, 16, 2048, 768)
    assert shapes["mtp"]["w_eh"].shape == (4096, 2048)
    assert family.vocab_size == 16160
    (call,) = family.attention_calls
    assert call.shapes(8192) == ((32, 8192, 192), (32, 8192, 192),
                                 (32, 8192, 128))


def test_model_flops_by_hand(config):
    S = 8192
    met = cost_joyai.layer_matmul_params(config)
    assert met == {"mla": 26_345_472, "dense": 44_040_192,
                   "experts": 2048 * 256 + 4_718_592 * (1 + 8 * 16 / 256)}
    assert cost_joyai.layers(config) == (7, 1, 6)
    matmuls = 7 * met["mla"] + met["dense"] + 6 * met["experts"] \
        + 2 * 16160 * 2048 + 2 * 2048 * 2048
    assert round(matmuls / 1e6, 1) == 348.7
    want = 6.0 * matmuls + 3.0 * 7 * S * 32 * (192 + 128)
    assert cost_joyai.model_flops_per_token(config, S) == want
    assert round(want / 1e9, 3) == 3.854
    # the program's own count agrees, here and at the tiny size
    for cfg, seq in ((config, S), (_tiny_family()[0], 128)):
        hybrid, model = spec.load_module(
            "models", "joyai_llm_flash").model_config(cfg, seq)
        assert hybrid.flops_per_token(model) \
            == cost_joyai.model_flops_per_token(cfg, seq)
        assert hybrid.num_params(model) == cost_joyai.params_held(cfg)


def test_a_calls_flops_and_bytes(config):
    """Forward 2 x (192 + 128) a pair, backward 2 x (3 x 192 + 2 x 128),
    over the causal half; q and k (dq, dk) at 192, v and o (do, dv) at 128:
    ``lib/cost.py`` over the kind the adapter states.  At one head dimension
    of 160, which the adapter stated until PR 51, the forward and the bytes
    come out the same and the backward 4 % low."""
    B, H, S = 1, 32, 8192
    pairs = B * H * S * S / 2
    (call,) = spec.load_module("models", "joyai_llm_flash").build(
        config, S).attention_calls
    fwd = cost.attention_call_cost("fwd", B, call, S)
    bwd = cost.attention_call_cost("bwd", B, call, S)
    assert fwd == (640 * pairs, B * H * S * 2 * (2 * 192 + 2 * 128))
    assert bwd == (1664 * pairs, B * H * S * 2 * (4 * 192 + 4 * 128))
    assert cost.attention_call_cost("fwd", B, causal(H, H, 160), S) == fwd
    low = cost.attention_call_cost("bwd", B, causal(H, H, 160), S)
    assert low[1] == bwd[1] and low[0] / bwd[0] == pytest.approx(1600 / 1664)
    seconds, bound = cost.least_time(*bwd, 197e12, 819e9)
    assert bound == "compute" and seconds == bwd[0] / 197e12
    with pytest.raises(ValueError):
        cost.attention_call_cost("both", B, call, S)


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


@pytest.mark.parametrize("seed", [0, 2])
def test_the_control_is_refused(seed):
    """``tools/control.py``'s control, the reference on weights rounded to 8
    bits, in the program's place at the seed's parameters: refused where the
    program passes (CPU, S=128: the program's median 0.008-0.011, the
    control's 0.060-0.061)."""
    control = spec.load_module("tools", "control").control
    _, family = _tiny_family()
    limit = 0.025
    mesh = make_mesh(MeshSpec(), jax.local_devices()[:1])
    rows = np.random.default_rng(seed).integers(
        0, family.vocab_size, (1, 129)).astype(np.int32)
    program = correct.at_the_seed(family, mesh, seed, rows, limit)
    refused = correct.at_the_seed(control(family), mesh, seed, rows, limit)
    assert program["grad_norm_err_median"] < limit / 2
    assert not refused["ok"] \
        and refused["grad_norm_err_median"] > 2 * limit


def test_the_cell_rehearses_with_every_new_metric(tmp_path):
    """``--rehearse --trace 1`` on the CPU: ``correct`` true, and every new
    per-layer metric's reader runs: the times and shares (which a CPU run
    never prints) are read from a trace that has no device plane and come
    back None without raising; the counts the cell shares with the other
    expert cells are printed."""
    bench = spec.load_benchmark()
    cell = spec.load_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "packed-s8192-b1", CONFIG)
    assert {m["name"] for m in cell["metrics"]["per_layer"]
            if m.get("workloads") == [CELL]} == NEW_METRICS
    assert SHARED_METRICS <= {m["name"] for m in cell["metrics"]["per_layer"]
                              if CELL in m.get("workloads", ())}
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
        assert (reader.UNIT, reader.SOURCE, reader.MOVES) == next(
            (m["unit"], m["source"], m["moves"]) for m in bench["per_layer"]
            if m["name"] == name)


def test_the_adapter_stops_at_once_where_the_kinds_are_missing(monkeypatch,
                                                               config):
    """On a checkout whose ``hybrid.KINDS`` has no ``L`` (the parent of PR
    47) the family says so and exits: no hang, no traceback."""
    from ray_tpu.models import hybrid

    monkeypatch.setattr(hybrid, "KINDS", {
        k: v for k, v in hybrid.KINDS.items() if k not in "LD"})
    with pytest.raises(SystemExit, match="no layer kind L or D"):
        spec.load_module("models", "joyai_llm_flash").build(config, 8192)
