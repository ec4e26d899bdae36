"""Family ``nemotron_h``: the configuration against the catalog's row, the
parameter count, the cost functions' arithmetic, and the cell's rehearsal.
(The program against ``reference/nemotron_h.py`` is tier 1's
``tests/test_nemotron_h.py``.)"""

import json
import os

import jax
import pytest

from benchmarks.lib import cost_nemotron, spec
from benchmarks.tests.test_run import result_line, run

CELL = "nemotron-ep16-s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"step.ssm_ms", "step.ssm_scan_ms", "step.ssm_scan_roofline",
               "step.ssm_conv_ms", "step.moe_shared_ms", "step.moe_routed_ms",
               "step.moe_routed_rows"}


@pytest.fixture(scope="module")
def config():
    return spec.load_json(spec.BENCH_DIR, "configs",
                          "nemotron-3-nano-30b-a3b-l9-ep16.json")


def test_only_the_stated_keys_differ_from_the_source(config):
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert config["source"] == row["source_url"]
    published = row["config"]
    differ = {k for k, v in published.items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    assert config["hybrid_override_pattern"] \
        == published["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert config["num_hidden_layers"] == 9
    assert config["n_routed_experts_published"] \
        == published["n_routed_experts"] == 128
    first, stop = config["experts_held"]
    assert (first, stop - first) == (0, config["n_routed_experts"]) == (0, 8)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert {"no_rotary", "router_bias", "initialisation", "init_seed",
            "lr_warmup_steps"} <= set(config["assumed"])
    assert "16 chips share each expert layer" in config["stands_for"]


def test_parameters_are_the_issues_arithmetic(config):
    family = spec.load_module("models", "nemotron_h").build(config, 8192)
    shapes = jax.eval_shape(family.init_fn, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    mamba = 2688 * 10304 + 6144 * 4 + 6144 + 3 * 64 + 4096 + 4096 * 2688 \
        + 2688
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    experts = 2688 * 128 + 2 * 2688 * 3712 + 2688 + 8 * 2 * 2688 * 1856
    assert n == 4 * mamba + attn + 4 * experts + 2 * 16384 * 2688 + 2688
    assert round(n / 1e6, 1) == 667.0
    assert n * 14 / 2 ** 30 == pytest.approx(8.70, abs=0.01)  # GiB of state
    assert shapes["ssm"]["in_proj"].shape == (4, 2688, 10304)
    assert shapes["experts"]["router"].shape == (4, 2688, 128)
    assert shapes["experts"]["w_up"].shape == (4, 8, 2688, 1856)
    assert "w_gate" not in shapes["experts"]
    assert shapes["attn"]["wq"].shape == (1, 2688, 4096)
    assert family.vocab_size == 16384 and family.eod_id == 2


def test_model_flops_by_hand_at_the_tiny_size():
    tiny = spec.load_json(spec.BENCH_DIR, "configs", "tiny-nemotron-h.json")
    S = 128
    D, H, P, G, N, Q = 64, 8, 16, 2, 16, 32
    mamba = D * (2 * H * P + 2 * G * N + H) + H * P * D
    attn = 2 * D * 32 * (4 + 2)
    experts = D * 16 + 2 * D * 96 + 2 * (4 / 16) * 2 * D * 48
    scan = 2.0 * (G * Q * N / 2 + H * Q * P / 2 + 2 * H * P * N)
    assert cost_nemotron.layer_matmul_params(tiny) == {
        "M": mamba, "*": attn, "E": experts}
    assert cost_nemotron.scan_flops_per_position(tiny, S) == scan
    want = 6.0 * (4 * mamba + attn + 4 * experts + 512 * D) \
        + 6.0 * S * 4 * 32 + 3.0 * 4 * scan
    assert cost_nemotron.model_flops_per_token(tiny, S) == want
    # the program's own count agrees
    hybrid, model = spec.load_module("models", "nemotron_h").model_config(
        tiny, S)
    assert hybrid.flops_per_token(model) == want


def test_scan_cost_counts_the_passes(config):
    tokens, S = 16384, 8192
    flops, nbytes = cost_nemotron.scan_step_cost(config, tokens, S, 1.0)
    assert flops == 4 * tokens * cost_nemotron.scan_flops_per_position(
        config, S)
    # x and y, B and C in bf16, delta in float32, the states out and in
    assert nbytes == 4 * tokens * (
        (2 * 4096 + 2 * 1024) * 2 + 64 * 4 + 2 * 64 * 64 * 128 * 4 / 128)
    again = cost_nemotron.scan_step_cost(config, tokens, S, 4.0)
    assert again == (4 * flops, 4 * nbytes)
    seconds, bound = cost_nemotron.scan_least_time(config, tokens, S, 4.0,
                                                   197e12, 819e9)
    assert bound == "memory" and seconds == 4 * nbytes / 819e9


def test_the_cell_rehearses_with_every_new_metric(tmp_path):
    """``--rehearse --trace 1`` on the CPU: ``correct`` true, and every new
    per-layer metric's reader runs: the counts are printed, the times and
    shares (which a CPU run never prints) are read from a trace that has no
    device plane and come back None without raising."""
    bench = spec.load_benchmark()
    cell = spec.load_cell(bench, CELL)
    # (later cells appended themselves to two of the lists, PR 43)
    assert {m["name"] for m in cell["metrics"]["per_layer"]
            if m.get("workloads", [None])[0] == CELL} == NEW_METRICS
    line = result_line(run(spec.ROOT, "--workload", CELL, "--seed",
                           "3987654321", "--seconds", "1", "--trace", "1",
                           "--rehearse"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["step.moe_routed_rows"]["value"] > 0
    assert line["metrics"]["step.compiles_in_window"]["value"] == 0
    for name in NEW_METRICS:
        reader = spec.load_module("layer_metrics", name)
        assert (reader.UNIT, reader.SOURCE, reader.MOVES) == next(
            (m["unit"], m["source"], m["moves"]) for m in bench["per_layer"]
            if m["name"] == name)
