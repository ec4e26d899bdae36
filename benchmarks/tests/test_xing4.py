"""Family ``xing4_0``: the configuration against the catalog's row, the
parameter count, the cost functions' arithmetic (the maps' product, the
least bytes of a read and a write), the five new readers on a made-up record,
the reference against the program at the rehearsal preset, the 8-bit
control, and the cell's rehearsal.  (The maps against the reference's loop,
the reduction to the one-stream model, the share test and YaRN's table are
tier 1's ``tests/test_xing4.py``.)"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import correct, cost, cost_joyai, cost_xing, spec
from benchmarks.lib.peaks import PEAKS
from benchmarks.tests.test_run import result_line, run
from ray_tpu.parallel import MeshSpec, make_mesh

CELL = "xing4-ep8-s4096"
CONFIG = "xing4.0-29b-a4b-l5-ep8"
FAMILY = "xing4_0"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"step.mhc_ms", "step.mhc_maps_ms", "step.mhc_mix_ms",
               "step.mhc_mix_roofline", "step.mhc_sinkhorn_err"}
SHARED_METRICS = {"step.moe_held_rows", "step.moe_load_max",
                  "step.moe_moved_rows", "step.moe_shared_ms",
                  "step.moe_routed_ms", "kernels.gmm_held_ms",
                  "kernels.gmm_held_roofline", "step.latent_ms",
                  "step.mtp_ms", "step.done_period_ms",
                  "step.done_period_spread", "trainer.starved_dispatches"}
REDUCED = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size"}
PEAKS_V5E = PEAKS["TPU v5 lite"]
S, TOKENS = 4096, 8192


@pytest.fixture(scope="module")
def config():
    return spec.load_json(spec.BENCH_DIR, "configs", CONFIG + ".json")


def _tiny_family(dtype="bfloat16"):
    tiny = spec.load_json(spec.BENCH_DIR, "configs", "tiny-xing4.json")
    tiny["options"] = {"attn_impl": "xla", "dtype": jnp.dtype(dtype),
                       "logits_dtype": jnp.dtype(dtype)}
    return tiny, spec.load_module("models", FAMILY).build(tiny, 128)


def test_only_the_stated_keys_differ_from_the_source(config):
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    assert config["source"] == row["source_url"]
    published = row["config"]
    differ = {k for k, v in published.items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == REDUCED
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert set(entry["reduced"]) == REDUCED
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] == row["source_url"]
    # every published width, and the five keys of the hyper-connections
    assert [config[k] for k in (
        "hidden_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "intermediate_size",
        "moe_intermediate_size", "num_experts_per_tok",
        "num_nextn_predict_layers", "num_attention_heads")] \
        == [3584, 768, 512, 128, 64, 128, 9216, 1024, 4, 1, 32]
    assert [config[k] for k in (
        "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
        "mhc_h_res_clamp_max")] == [4, 20, 1e-6, -30, 30]
    assert config["rope_scaling"] == published["rope_scaling"]
    assert (config["num_hidden_layers"], config["first_k_dense_replace"]) \
        == (5, 1)
    assert config["n_routed_experts_published"] \
        == published["n_routed_experts"] == 64
    assert config["experts_held"] == [0, 8]
    assert config["vocab_size"] * 8 == published["vocab_size"] \
        == config["vocab_size_published"]
    assert config["eos_token_id"] < config["vocab_size"]
    assert {"equations", "stream_maps", "stack_ends", "prediction_module",
            "rope_pairing", "router_bias", "initialisation", "init_seed",
            "lr_warmup_steps", "training_dtype"} <= set(config["assumed"])
    assert config["lr_warmup_steps"] == 2000 and config["init_seed"] == 62
    assert config["mtp_loss_weight"] == 0.3
    assert "eight chips" in config["stands_for"]
    assert config["check"]["seed_grad_tol"] and config["check_why"]
    assert config["rehearse_with"] == "tiny-xing4"
    assert spec.load_module("models", "joyai_llm_flash").pattern(config) \
        == "LD" + "LE" * 4


def test_parameters_are_the_issues_table(config):
    family = spec.load_module("models", FAMILY).build(config, S)
    shapes = jax.eval_shape(family.init_fn, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    latent = 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584
    expert = 3 * 3584 * 1024
    dense = 3 * 3584 * 9216
    maps = 4 * 3584 * 24 + 24 + 3
    experts = 3584 * 64 + expert + 8 * expert
    assert (latent, expert, dense, maps) == (
        28_409_856, 11_010_048, 99_090_432, 344_091)
    norms = 6 * (3584 + 768 + 512) + 6 * 3584 + 3584 + 3 * 3584
    assert n == 6 * latent + dense + 5 * experts + 2 * 16384 * 3584 \
        + 2 * 3584 * 3584 + 12 * maps + norms == cost_xing.params_held(config)
    assert round(n / 1e6, 1) == 913.5                    # ISSUE 62: 913.4
    assert n * 10 / 1e9 == pytest.approx(9.13, abs=0.01)  # GB resident
    assert shapes["hc"]["phi"].shape == (12, 4 * 3584, 24)
    assert shapes["hc"]["alpha"].shape == (12, 3)
    assert shapes["hc"]["base"].shape == (12, 24)
    assert shapes["mla"]["wq_b"].shape == (6, 768, 32 * 192)
    assert shapes["dense"]["w_gate"].shape == (1, 3584, 9216)
    assert shapes["experts"]["router"].shape == (5, 3584, 64)
    assert shapes["experts"]["w_gate"].shape == (5, 8, 3584, 1024)
    assert shapes["mtp"]["w_eh"].shape == (2 * 3584, 3584)
    assert family.vocab_size == 16384
    (call,) = family.attention_calls
    assert call.shapes(S) == ((32, S, 192), (32, S, 192), (32, S, 128))


def test_model_flops_by_hand(config):
    """``cost_joyai``'s layers, the maps' product on twelve sub-layers, and
    ``w_eh`` at the embedding once and the hidden state a stream."""
    met = cost_joyai.layer_matmul_params(config)
    assert met == {"mla": 28_409_856, "dense": 99_090_432,
                   "experts": 3584 * 64 + 11_010_048 * (1 + 4 * 8 / 64)}
    assert cost_joyai.layers(config) == (6, 1, 5)
    assert cost_xing.sublayers(config) == 12
    assert cost_xing.maps_matmul_params(config) == 4 * 3584 * 24 == 344_064
    matmuls = 6 * met["mla"] + met["dense"] + 5 * met["experts"] \
        + 2 * 16384 * 3584 + 5 * 3584 * 3584 + 12 * 344_064
    assert round(matmuls / 1e6, 1) == 539.1
    want = 6.0 * matmuls + 3.0 * 6 * S * 32 * (192 + 128)
    assert cost_xing.model_flops_per_token(config, S) == want
    assert round(want / 1e9, 3) == 3.989
    for cfg, seq in ((config, S), (_tiny_family()[0], 128)):
        hybrid, model = spec.load_module("models", FAMILY).model_config(
            cfg, seq)
        assert hybrid.flops_per_token(model) \
            == cost_xing.model_flops_per_token(cfg, seq)
        assert hybrid.num_params(model) == cost_xing.params_held(cfg)
    # the one attention call's cost is lib/cost.py's at 192 / 128
    (call,) = spec.load_module("models", FAMILY).build(
        config, S).attention_calls
    assert cost.attention_call_cost("fwd", 2, call, S)[0] \
        == 640 * 2 * 32 * S * S / 2


def test_the_mixs_least_bytes_by_hand(config):
    """A sub-layer's forward a position: X read twice (8 x 3584), u written
    and y read (2 x 3584), X' written (4 x 3584), bf16: 100,352 bytes; the
    backward 27 such arrays for the forward's 14, a recomputed read 5;
    twelve sub-layers."""
    array = 3584 * 2
    assert 14 * array == 100_352
    assert cost_xing.mix_bytes(config, TOKENS, False) \
        == 12 * TOKENS * (14 + 27) * array == 28_890_365_952
    assert cost_xing.mix_bytes(config, TOKENS, True) \
        == 12 * TOKENS * (14 + 27 + 5) * array
    assert 1e3 * cost_xing.mix_bytes(config, TOKENS, True) \
        / PEAKS_V5E.hbm_bw == pytest.approx(39.58, abs=0.01)


def _made_up(config, table, rows=()):
    made = types.SimpleNamespace(
        cell={"config_file": config}, peaks=PEAKS_V5E, chips=1,
        tokens_per_step=TOKENS, seq_len=S, profiler_rows=list(rows))
    made.anatomy = {name: tuple(name.split("/")) for name in table}
    made.self_seconds = {name: ms / 1e3 for name, ms in table.items()}
    made.steady = (0.0, 1.0, 1, [1.0])
    made.trace = types.SimpleNamespace(first=types.SimpleNamespace(ops=[]))
    return made


def test_the_new_readers_on_a_made_up_record(config):
    readers = {name: spec.load_module("layer_metrics", name)
               for name in NEW_METRICS}
    table = {"forward/mhc": 1.0, "forward/mhc_maps": 10.0,
             "backward/mhc_maps": 25.0, "forward/mhc_mix": 20.0,
             "backward/mhc_mix": 40.0, "forward/attn": 7.0}
    rows = [{"mhc_sinkhorn_err": [1e-6] * 11 + [3e-4]},
            {"mhc_sinkhorn_err": [2e-5] * 12}]
    made_up = _made_up(config, table, rows)
    assert readers["step.mhc_ms"].read(made_up) == pytest.approx(96.0)
    assert readers["step.mhc_maps_ms"].read(made_up) == pytest.approx(35.0)
    assert readers["step.mhc_mix_ms"].read(made_up) == pytest.approx(60.0)
    roofline = readers["step.mhc_mix_roofline"]
    # over the three parts' time, not the mix's alone
    assert roofline.read(made_up) == pytest.approx(100 * 35.274 / 96.0,
                                                   rel=1e-3)
    assert roofline.describe(made_up)["recomputed"] is False
    again = _made_up(config, dict(table, **{"recompute/mhc_mix": 20.0,
                                            "recompute/mhc_maps": 10.0}), rows)
    assert roofline.read(again) == pytest.approx(100 * 39.58 / 126.0,
                                                 rel=1e-3)
    assert roofline.describe(again) == {
        "least_ms": pytest.approx(39.58, rel=1e-3), "bound_by": "memory",
        "over_ms": pytest.approx(126.0),
        "recomputed": True}
    assert set(readers["step.mhc_ms"].describe(again)) \
        == set(again.anatomy) - {"forward/attn"}
    err = readers["step.mhc_sinkhorn_err"]
    assert err.read(made_up) == pytest.approx(3e-4)
    said = err.describe(made_up)
    assert said["rows"] == 2 and len(said["per_sublayer_max"]) == 12
    assert said["per_sublayer_max"][-1] == pytest.approx(3e-4)
    # a program without the scopes and the counter (the parent of PR 62):
    # nothing, no raise
    bare = _made_up(config, {"forward/attn": 7.0, "backward/mlp": 9.0},
                    [{"moe_rows": [[[1]]]}])
    for reader in readers.values():
        assert reader.read(bare) is None
        assert not reader.describe(bare)
    nothing = types.SimpleNamespace(anatomy=None, trace=None, steady=None,
                                    peaks=PEAKS_V5E, cell={},
                                    profiler_rows=[])
    assert all(reader.read(nothing) is None for reader in readers.values())


@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    ("float32", 1e-5, 2e-4), ("bfloat16", correct.LOSS_TOL,
                              correct.GRAD_TOL)], ids=["float32", "bfloat16"])
def test_the_program_matches_the_reference_at_the_rehearsal_preset(
        dtype, loss_tol, grad_tol):
    _, family = _tiny_family(dtype)
    params = jax.jit(family.init_fn)(jax.random.key(0))
    # the maps off their start: logits a few units wide
    params["hc"] = {"phi": params["hc"]["phi"] * 5.0,
                    "alpha": params["hc"]["alpha"] * 100.0,
                    "base": params["hc"]["base"] * 0.1}
    rows = np.random.default_rng(1).integers(
        0, family.vocab_size, (2, 129)).astype(np.int32)
    tokens, targets = rows[:, :-1], rows[:, 1:]
    loss, grads = jax.jit(jax.value_and_grad(family.loss_fn))(
        params, tokens, targets)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p, t, y: family.reference_loss(p, t, y, 64)))(
        params, tokens, targets)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < loss_tol
    assert set(grads["hc"]) == {"phi", "alpha", "base"}
    for path, (a, b) in jax.tree_util.tree_flatten_with_path(
            jax.tree.map(lambda a, b: (a, b), grads, ref_grads),
            is_leaf=lambda x: isinstance(x, tuple))[0]:
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                    / jnp.max(jnp.abs(b)))
        assert err < grad_tol, (jax.tree_util.keystr(path), err)


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(spec.BENCH_DIR, "reference", "xing4_0.py")) as f:
        source = f.read()
    assert "import ray_tpu" not in source and "from ray_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "range(cfg[\"hc_sinkhorn_iters\"])" in source  # the explicit loop


@pytest.mark.parametrize("seed", [0, 2])
def test_the_control_is_refused(seed):
    """``tools/control.py``'s control, the reference on weights rounded to 8
    bits, in the program's place at the seed's parameters: refused where the
    program passes."""
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


def test_the_cell_rehearses_with_every_new_metric():
    """``--rehearse --trace 1`` on the CPU: ``correct`` true, and every new
    per-layer metric's reader runs: the times and shares (which a CPU run
    never prints) come back None without raising; the counts the cell shares
    with the other expert cells are printed."""
    bench = spec.load_benchmark()
    cell = spec.load_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "packed-s4096-b2", CONFIG)
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
    for name in NEW_METRICS:
        reader = spec.load_module("layer_metrics", name)
        assert (reader.UNIT, reader.SOURCE, reader.MOVES) == next(
            (m["unit"], m["source"], m["moves"]) for m in bench["per_layer"]
            if m["name"] == name)


def test_the_adapter_stops_at_once_where_the_streams_are_missing(monkeypatch,
                                                                 config):
    """On a checkout whose ``HybridConfig`` has no ``streams`` (the parent of
    PR 62) the family says so and exits: no hang, no traceback."""
    import dataclasses

    from ray_tpu.models import hybrid

    @dataclasses.dataclass(frozen=True)
    class Parents:
        pattern: str = "LD"

    monkeypatch.setattr(hybrid, "HybridConfig", Parents)
    with pytest.raises(SystemExit, match="no residual of several streams"):
        spec.load_module("models", FAMILY).build(config, S)


def test_the_adapter_refuses_what_the_program_does_not_implement(config):
    build = spec.load_module("models", FAMILY).build
    for key, value, says in (
            ("rope_scaling", dict(config["rope_scaling"], type="linear"),
             "rope_scaling"),
            ("hc_mult", 1, "plain residual"),
            ("n_shared_experts", 2, "n_shared_experts"),
            ("num_nextn_predict_layers", 2, "one prediction module")):
        with pytest.raises(SystemExit, match=says):
            build(dict(config, **{key: value}), S)
