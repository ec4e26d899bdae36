"""Family ``solar_open2``: the configuration against the catalog's row, the
parameter count, the cost functions' arithmetic, and the cell's rehearsal.
(The program against ``reference/solar_open2.py`` is tier 1's
``tests/test_solar_open2.py``.)"""

import json
import os

import jax
import pytest

from benchmarks.lib import cost_solar, spec
from benchmarks.tests.test_run import result_line, run

CELL = "solar-open2-ep40-tp8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"step.kda_ms", "step.kda_scan_ms", "step.kda_conv_ms",
               "step.kda_scan_roofline"}
REDUCED = {"num_hidden_layers", "n_routed_experts", "linear_attn_config",
           "num_attention_heads", "num_key_value_heads", "vocab_size"}


@pytest.fixture(scope="module")
def config():
    return spec.load_json(spec.BENCH_DIR, "configs",
                          "solar-open2-250b-l4-ep40-tp8.json")


def test_only_the_stated_keys_differ_from_the_source(config):
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    assert config["source"] == row["source_url"]
    published = row["config"]
    differ = {k for k, v in published.items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == REDUCED
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "solar-open2-250b-l4-ep40-tp8")
    assert set(entry["reduced"]) == REDUCED
    # inside the one nested group only the head count changes: no width
    assert config["linear_attn_config"] == dict(
        published["linear_attn_config"], num_heads=8)
    assert config["num_hidden_layers"] == 4
    assert [i for i in config["gqa_layers"] if i < 4] == [0]
    assert config["n_routed_experts_published"] \
        == published["n_routed_experts"] == 320
    assert config["experts_held"] == config["heads_held"] == [0, 8]
    assert (config["num_attention_heads"], config["num_key_value_heads"]) \
        == (8, 1)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert {"kda_equations", "gqa_equations", "expert_equations",
            "initialisation", "init_seed", "lr_warmup_steps"} \
        <= set(config["assumed"])
    # the optimizer's one key is the issued warm-up, as the other hybrid has it
    assert config["lr_warmup_steps"] == 2000 and "learning_rate" not in config
    assert "forty chips share" in config["stands_for"]
    assert config["check"]["seed_grad_tol"] and config["check_why"]


def test_parameters_are_the_issues_arithmetic(config):
    family = spec.load_module("models", "solar_open2").build(config, 8192)
    shapes = jax.eval_shape(family.init_fn, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    kda = 3 * 4096 * 1024 + 1024 * 4096 + 2 * (4096 * 128 + 128 * 1024) \
        + 1024 + 4096 * 8 + 3 * 4 * 1024 + 8 + 1024 + 128 + 4096
    attn = 3 * 4096 * 1024 + 2 * 4096 * 128 + 4096
    experts = 4096 * 320 + 3 * 4096 * 1280 + 4096 + 8 * 3 * 4096 * 1280
    assert (kda, attn, experts) == (18_139_272, 13_635_584, 142_872_576)
    assert n == 3 * kda + attn + 4 * experts + 2 * 24576 * 4096 + 4096
    assert round(n / 1e6, 1) == 840.9  # ISSUE 43 wrote 840.8, truncated
    assert n * 14 / 2 ** 30 == pytest.approx(10.96, abs=0.01)  # GiB of state
    assert shapes["kda"]["wq"].shape == (3, 4096, 1024)
    assert shapes["kda"]["w_fa"].shape == (3, 4096, 128)
    assert shapes["attn"]["wg"].shape == (1, 4096, 1024)
    assert shapes["attn"]["wk"].shape == (1, 4096, 128)
    assert shapes["experts"]["router"].shape == (4, 4096, 320)
    assert shapes["experts"]["w_gate"].shape == (4, 8, 4096, 1280)
    assert shapes["experts"]["shared_gate"].shape == (4, 4096, 1280)
    assert family.vocab_size == 24576 and family.eod_id == 2


def test_model_flops_by_hand_at_the_tiny_size():
    tiny = spec.load_json(spec.BENCH_DIR, "configs", "tiny-solar-open2.json")
    S = 128
    D, H, d, C = 64, 2, 16, 32
    kda = 4 * D * H * d + 2 * (D * d + d * H * d) + D * H
    attn = D * 32 * (3 * 2 + 2 * 1)
    experts = D * 16 + 3 * D * 48 * (1 + 2 * 4 / 16)
    scan = 2.0 * H * (2.5 * C * d + 3 * d * d)
    assert cost_solar.layers(tiny) == (3, 1)
    assert cost_solar.layer_matmul_params(tiny) == {
        "kda": kda, "attn": attn, "experts": experts}
    assert cost_solar.scan_flops_per_position(tiny, S) == scan
    want = 6.0 * (3 * kda + attn + 4 * experts + 512 * D) \
        + 6.0 * S * 2 * 32 + 3.0 * 3 * scan
    assert cost_solar.model_flops_per_token(tiny, S) == want
    # the program's own count agrees
    hybrid, model = spec.load_module("models", "solar_open2").model_config(
        tiny, S)
    assert hybrid.flops_per_token(model) == want


def test_scan_cost_counts_the_passes(config):
    tokens, S = 8192, 8192
    # a position a head: A and B at the causal half, T [V | Kbar] at the
    # triangular half, B U, three d x d products with the state
    a_head = 2 * (64 * 128 / 2 * 2 + 64 * 256 / 2 + 64 * 128 / 2
                  + 3 * 128 * 128)
    assert a_head == 139264
    assert cost_solar.scan_flops_per_position(config, S) == 8 * a_head
    flops, nbytes = cost_solar.scan_step_cost(config, tokens, S, 1.0)
    assert flops == 3 * tokens * 8 * a_head
    # q, k, v and o in bf16, g and beta in float32, the states out and in
    assert nbytes == 3 * tokens * 8 * (
        4 * 128 * 2 + 128 * 4 + 4 + 2 * 128 * 128 * 4 / 64)
    again = cost_solar.scan_step_cost(config, tokens, S, 4.0)
    assert again == (4 * flops, 4 * nbytes)
    seconds, bound = cost_solar.scan_least_time(config, tokens, S, 4.0,
                                                197e12, 819e9)
    assert bound == "memory" and seconds == 4 * nbytes / 819e9


def test_the_cell_rehearses_with_every_new_metric(tmp_path):
    """``--rehearse --trace 1`` on the CPU: ``correct`` true, and every new
    per-layer metric's reader runs: the times and shares (which a CPU run
    never prints) are read from a trace that has no device plane and come
    back None without raising."""
    bench = spec.load_benchmark()
    cell = spec.load_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "epochs8-s8192-b1")
    assert {m["name"] for m in cell["metrics"]["per_layer"]
            if m.get("workloads") == [CELL]} == NEW_METRICS
    line = result_line(run(spec.ROOT, "--workload", CELL, "--seed",
                           "3987654321", "--seconds", "1", "--trace", "1",
                           "--rehearse"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["step.compiles_in_window"]["value"] == 0
    assert not NEW_METRICS & set(line["metrics"])
    for name in NEW_METRICS:
        reader = spec.load_module("layer_metrics", name)
        assert (reader.UNIT, reader.SOURCE, reader.MOVES) == next(
            (m["unit"], m["source"], m["moves"]) for m in bench["per_layer"]
            if m["name"] == name)
