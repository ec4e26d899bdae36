"""Family ``olmo_hybrid``: the configuration against the catalog's row, the
parameter count against ISSUE 58's table, the cost file by hand, the four
new readers on a made-up record and on a trace recorded on the chip
(``testdata/tiny-olmo-hybrid-named.*``: the cell's rehearsal on a TPU v5e,
``run.py --workload olmo-hybrid-s8192 --rehearse --trace 1 --keep-trace``,
the step's ``anatomy()`` asked of the same process), ``attention_calls``, the
reference against the program at the rehearsal preset, and the cell's
rehearsal.  (The kind, the norm placement and the scan against their
written-out formulas are tier 1's ``tests/test_olmo_hybrid.py`` and
``tests/test_gdn.py``.)"""

import json
import os
import types

import jax
import numpy as np
import pytest

from benchmarks.lib import anatomy, correct, cost_olmo_hybrid, spec
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.peaks import PEAKS
from benchmarks.lib.record import RunRecord
from benchmarks.tests.test_attention_calls import allowed_by_the_program
from benchmarks.tests.test_run import result_line, run
from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh

CELL = "olmo-hybrid-s8192"
CONFIG = "olmo-hybrid-7b-l4"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"step.gdn_ms", "step.gdn_scan_ms", "step.gdn_conv_ms",
               "step.gdn_scan_roofline"}
SHARED_METRICS = {"step.done_period_ms", "step.done_period_spread",
                  "trainer.starved_dispatches"}
REDUCED = {"num_hidden_layers", "vocab_size"}
S, TOKENS = 8192, 8192
PEAKS_V5E = PEAKS["TPU v5 lite"]


@pytest.fixture(scope="module")
def config():
    return spec.load_json(spec.BENCH_DIR, "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def family(config):
    return spec.load_module("models", "olmo_hybrid").build(config, S)


def _tiny():
    tiny = spec.load_json(spec.BENCH_DIR, "configs", "tiny-olmo-hybrid.json")
    tiny["options"] = {"attn_impl": "xla"}
    return tiny


def test_only_the_stated_keys_differ_from_the_source(config):
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    assert config["source"] == row["source_url"]
    published = row["config"]
    differ = {k for k, v in published.items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == REDUCED
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert set(entry["reduced"]) == REDUCED
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    # every head and every width whole
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "intermediate_size", "linear_num_key_heads",
        "linear_num_value_heads", "linear_key_head_dim",
        "linear_value_head_dim", "linear_conv_kernel_dim")] \
        == [3840, 30, 30, 11008, 30, 30, 96, 192, 4]
    assert "heads_held" not in config  # the whole-heads cut, no fallback
    assert config["layer_types"] == published["layer_types"] \
        and len(config["layer_types"]) == 32
    assert config["num_hidden_layers"] == 4
    assert config["num_hidden_layers_published"] \
        == published["num_hidden_layers"] == 32
    assert config["vocab_size"] * 8 == published["vocab_size"] \
        == config["vocab_size_published"]
    assert config["eos_token_id"] < config["vocab_size"]
    assert config["rope_parameters"] == {"rope_theta": None}
    assert {"linear_equations", "output_gate", "norm_placement", "qk_norm",
            "rope", "gdn_chunk", "initialisation", "eos_token_id",
            "training_dtype", "lr_warmup_steps"} <= set(config["assumed"])
    # a dense model: the parameters come from --seed; the warm-up is the
    # chip's reading (3e-4 from the first step sent the loss to 18)
    assert "init_seed" not in config
    assert config["lr_warmup_steps"] == 500
    assert "eight-stage pipeline" in config["stands_for"]
    assert config["check"]["seed_grad_tol"] and config["check_why"]
    module = spec.load_module("models", "olmo_hybrid")
    assert module.pattern(config) == "GDGDGD*D"
    # at the published depth: 24 linear layers and 8 full ones, a D after
    # each
    whole = module.pattern(dict(config, num_hidden_layers=32))
    assert (whole.count("G"), whole.count("*"), whole.count("D")) \
        == (24, 8, 32)
    assert cost_olmo_hybrid.layers(config) == (3, 1)


def test_parameters_are_the_issues_table(config, family):
    shapes = jax.eval_shape(family.init_fn, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    linear = 2 * 3840 * 2880 + 2 * 3840 * 5760 + 5760 * 3840 \
        + 2 * 3840 * 30 + 4 * 11520
    full = 4 * 3840 * 3840
    mlp = 3 * 3840 * 11008
    embed = 2 * 12544 * 3840
    # ISSUE 58's rows: 88.8 M, 59.0 M, 126.8 M, a period 832.5 M, 96.3 M
    assert [round(x / 1e6, 1) for x in (
        linear, full, mlp, 3 * (linear + mlp) + full + mlp, embed)] \
        == [88.8, 59.0, 126.8, 832.5, 96.3]
    matrices = 3 * linear + full + 4 * mlp + embed
    assert round(matrices / 1e6, 1) == 928.8
    # beside them the norms (one a sub-layer, a head's, q's and k's, the
    # final one) and the decay's two vectors a head
    vectors = 3 * (3840 + 192 + 2 * 30) + (3840 + 2 * 3840) + 4 * 3840 \
        + 3840
    assert n == matrices + vectors == cost_olmo_hybrid.params_held(config)
    assert round(n * 14 / 1e9, 1) == 13.0       # GB of state and gradients
    assert round(n * 10 / 1e9, 1) == 9.3        # of it resident
    assert shapes["gdn"]["wq"].shape == (3, 3840, 30 * 96)
    assert shapes["gdn"]["wv"].shape == shapes["gdn"]["wg"].shape \
        == (3, 3840, 30 * 192)
    assert shapes["gdn"]["wo"].shape == (3, 30 * 192, 3840)
    assert shapes["gdn"]["conv_k"].shape == (3, 4, 2880)
    assert shapes["gdn"]["A_log"].shape == shapes["gdn"]["dt_bias"].shape \
        == (3, 30)
    assert shapes["attn"]["wk"].shape == (1, 3840, 3840)
    assert shapes["attn"]["q_norm"].shape == (1, 3840)
    assert shapes["dense"]["w_gate"].shape == (4, 3840, 11008)
    assert shapes["lm_head"].shape == shapes["wte"].shape == (12544, 3840)
    assert "experts" not in shapes and family.vocab_size == 12544


def test_model_flops_are_the_programs_and_by_hand(config, family):
    from ray_tpu.models import hybrid

    _, model = spec.load_module("models", "olmo_hybrid").model_config(
        config, S)
    assert family.flops_per_token == hybrid.flops_per_token(model)
    linear = 3840 * 30 * (2 * 96 + 3 * 192 + 2)
    matmuls = 3 * linear + 4 * 3840 * 3840 + 4 * 3 * 3840 * 11008 \
        + 12544 * 3840
    scan = 2.0 * 30 * (64 * (1.5 * 96 + 192) + 3 * 96 * 192)
    assert scan == 4_608_000 == cost_olmo_hybrid.scan_flops_per_position(
        config, S)
    assert family.flops_per_token == 6.0 * matmuls + 6.0 * S * 3840 \
        + 3.0 * 3 * scan
    assert family.flops_per_token / 1e9 == pytest.approx(5.513, abs=0.001)
    # the head is 5.5 % of the matrix entries a token meets, the MLPs 58 %
    assert (round(matmuls / 1e6), round(1000 * 12544 * 3840 / matmuls),
            round(100 * 4 * 3 * 3840 * 11008 / matmuls)) == (881, 55, 58)


def test_the_scans_least_work_by_hand(config):
    """A position a head: q and k 96 x 2 bytes each way, v and o 192 x 2,
    g and beta 4 each; a chunk of 64 a head: the 96 x 192 float32 state out
    and in.  Three layers, three passes (four where the checkpoint runs the
    forward again)."""
    a_position = 30 * (2 * (96 + 192) * 2 + 8)
    states = 2 * 30 * 96 * 192 * 4 / 64
    assert (a_position, states) == (34_800, 69_120)
    for passes in (3.0, 4.0):
        flops, moved = cost_olmo_hybrid.scan_step_cost(config, TOKENS, S,
                                                       passes)
        assert flops == 3 * passes * TOKENS * 4_608_000
        assert moved == 3 * passes * TOKENS * (a_position + states)
    seconds, bound = cost_olmo_hybrid.scan_least_time(
        config, TOKENS, S, 4.0, PEAKS_V5E.flops, PEAKS_V5E.hbm_bw)
    assert bound == "memory"
    assert 1e3 * seconds == pytest.approx(12.47, abs=0.01)
    assert 1e3 * cost_olmo_hybrid.scan_least_time(
        config, TOKENS, S, 3.0, PEAKS_V5E.flops, PEAKS_V5E.hbm_bw)[0] \
        == pytest.approx(9.36, abs=0.01)
    # the products alone would take 2.3 ms at the bf16 peak
    assert 1e3 * 3 * 4.0 * TOKENS * 4_608_000 / PEAKS_V5E.flops \
        == pytest.approx(2.30, abs=0.01)


def _made_up(config, table):
    """A record whose anatomy table is ``table`` (ms a step by
    ``phase/part``), as the four new readers see one."""
    made = types.SimpleNamespace(
        cell={"config_file": config}, peaks=PEAKS_V5E, chips=1,
        tokens_per_step=TOKENS, seq_len=S)
    made.anatomy = {name: tuple(name.split("/")) for name in table}
    made.self_seconds = {name: ms / 1e3 for name, ms in table.items()}
    made.steady = (0.0, 1.0, 1, [1.0])
    made.trace = types.SimpleNamespace(first=types.SimpleNamespace(ops=[]))
    return made


def test_the_new_readers_on_a_made_up_record(config):
    readers = {name: spec.load_module("layer_metrics", name)
               for name in NEW_METRICS}
    table = {"forward/gdn": 20.0, "backward/gdn": 40.0,
             "forward/gdn_conv": 3.0, "backward/gdn_conv": 5.0,
             "forward/gdn_scan": 10.0, "backward/gdn_scan": 30.0,
             "forward/attn": 7.0}
    made_up = _made_up(config, table)
    assert readers["step.gdn_ms"].read(made_up) == pytest.approx(108.0)
    assert readers["step.gdn_conv_ms"].read(made_up) == pytest.approx(8.0)
    assert readers["step.gdn_scan_ms"].read(made_up) == pytest.approx(40.0)
    roofline = readers["step.gdn_scan_roofline"]
    assert roofline.read(made_up) == pytest.approx(100 * 9.356 / 40.0,
                                                   rel=1e-3)
    assert roofline.describe(made_up)["passes"] == 3.0
    again = _made_up(config, dict(table, **{"recompute/gdn_scan": 10.0}))
    assert roofline.read(again) == pytest.approx(100 * 12.473 / 50.0,
                                                 rel=1e-3)
    assert roofline.describe(again) == {
        "least_ms": pytest.approx(12.473, rel=1e-3), "bound_by": "memory",
        "passes": 4.0}
    assert set(readers["step.gdn_ms"].describe(again)) == set(again.anatomy) \
        - {"forward/attn"}
    # a program without the scopes (the parent of PR 58): nothing, no raise
    bare = _made_up(config, {"forward/attn": 7.0, "backward/mlp": 9.0})
    for reader in readers.values():
        assert reader.read(bare) is None
        assert not reader.describe(bare)
    nothing = types.SimpleNamespace(anatomy=None, trace=None, steady=None,
                                    peaks=PEAKS_V5E, cell={})
    assert all(reader.read(nothing) is None for reader in readers.values())


# ------------------------------------------------- readers, recorded trace
RECORDED = os.path.join(spec.BENCH_DIR, "testdata", "tiny-olmo-hybrid-named")


@pytest.fixture(scope="module")
def chip_run():
    trace = tr.load(RECORDED + ".xplane.pb.gz")
    with open(RECORDED + ".anatomy.json") as f:
        held = json.load(f)
    recorded = RunRecord(
        cell={"config_file": dict(_tiny(), gdn_chunk=32)}, chips=1,
        peaks=PEAKS_V5E, tokens_per_step=held["tokens_per_step"],
        flops_per_step=1.0, seq_len=held["seq_len"], attention_calls=(),
        trace=trace,
        steady=tr.steady_window(trace.first.modules,
                                tr.step_module(trace.first.modules)),
        hlo={"mosaic": held["mosaic"], "collectives": {}})
    recorded.anatomy = {k: tuple(v) for k, v in held["anatomy"].items()}
    return recorded


def test_the_four_readers_on_a_recorded_chip_trace(chip_run):
    def read(metric):
        return spec.load_module("layer_metrics", metric).read(chip_run)

    whole, scan, conv = (read("step.gdn_ms"), read("step.gdn_scan_ms"),
                         read("step.gdn_conv_ms"))
    assert 0 < conv < scan < whole
    assert whole == pytest.approx(anatomy.part_ms(
        chip_run, "gdn", "gdn_conv", "gdn_scan"))
    # nested scopes: the scan's time is not also the layer's own part's
    assert whole - scan - conv == pytest.approx(
        anatomy.part_ms(chip_run, "gdn"))
    # every phase of the scan was traced, the checkpoint's second forward
    # among them, so four passes are charged
    table = anatomy.table(chip_run)
    assert all(table.get(f"{phase}/gdn_scan", 0) > 0
               for phase in ("forward", "recompute", "backward"))
    roofline = spec.load_module("layer_metrics", "step.gdn_scan_roofline")
    note = roofline.describe(chip_run)
    assert note["passes"] == 4.0 and note["bound_by"] == "memory"
    # a tiny preset's scan is far from any peak, and never past one
    assert 0 < roofline.read(chip_run) < 5
    assert roofline.read(chip_run) == pytest.approx(
        100 * note["least_ms"] / scan)
    # the other parts of the step are other scopes'
    parts = sum(anatomy.part_ms(chip_run, p) for p in (
        "gdn", "gdn_conv", "gdn_scan", "attn", "attn_kernel", "mlp",
        "lm_head", "embed", "optimizer"))
    assert parts < 1e3 * tr.median(chip_run.steady[3])


def test_attention_calls_is_one_causal_kind_at_the_layers_heads(family):
    (call,) = family.attention_calls
    assert (call.name, call.q_heads, call.kv_heads, call.qk_dim,
            call.v_dim) == ("causal", 30, 30, 128, 128)
    assert call.pairs(S) == S * S / 2
    assert call.shapes(S) == ((30, S, 128),) * 3
    tiny = _tiny()
    (small,) = spec.load_module("models", "olmo_hybrid").build(
        tiny, 64).attention_calls
    allowed = allowed_by_the_program(tiny, 64)
    assert np.array_equal(allowed, np.tril(np.ones((64, 64), bool)))
    assert allowed.sum() - small.pairs(64) == 64 / 2


# ------------------------------------------------- program against reference
def test_the_program_matches_the_reference_at_the_rehearsal_preset():
    """Through the comparison the chip run uses, on parameters a few steps
    of a unit-scale residual stream away from the initialisation (the
    embedding times 50: at 0.02 the first sub-layers lie under the norms'
    eps and the bf16 leaves read 0.13 off, ``tests/test_olmo_hybrid.py``)."""
    family = spec.load_module("models", "olmo_hybrid").build(_tiny(), 128)
    params = jax.jit(family.init_fn)(jax.random.key(0))
    params["wte"] = params["wte"] * 50.0
    mesh = make_mesh(MeshSpec(), jax.local_devices()[:1])
    rows = np.random.default_rng(1).integers(
        0, family.vocab_size, (1, 129)).astype(np.int32)
    tokens, targets = (jax.device_put(a, batch_sharding(mesh))
                       for a in (rows[:, :-1], rows[:, 1:]))
    got = correct.compare(family, params, tokens, targets, mesh)
    assert got["ok"], got
    assert len(got["grad_err_by_leaf"]) == len(jax.tree.leaves(params))
    assert {"['gdn']['A_log']", "['gdn']['conv_v']", "['attn']['q_norm']",
            "['dense']['w_down']", "['lm_head']"} \
        <= set(got["grad_err_by_leaf"])
    assert 1e-4 < got["grad_err_max"] < 0.4  # bf16 against float32


def test_the_reference_imports_nothing_from_the_program():
    source = open(os.path.join(spec.BENCH_DIR, "reference",
                               "olmo_hybrid.py")).read()
    assert "ray_tpu" not in source.split('"""', 2)[2]
    assert "chunk" not in source.split('"""', 2)[2]  # position by position


def test_the_cell_rehearses_with_every_new_metric():
    """``--rehearse --trace 1`` on the CPU: ``correct`` true, and every new
    per-layer metric's reader runs: the times and the share (which a CPU run
    never prints) are read from a trace that has no device plane and come
    back None without raising; counts only are printed."""
    bench = spec.load_benchmark()
    cell = spec.load_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "packed-s8192-b1", CONFIG)
    assert len(cell["why"]) <= 200
    assert {m["name"] for m in cell["metrics"]["per_layer"]
            if m.get("workloads") == [CELL]} == NEW_METRICS
    assert SHARED_METRICS == {m["name"] for m in cell["metrics"]["per_layer"]
                              if CELL in m.get("workloads", ())
                              and m["workloads"] != [CELL]}
    # no other cell lists the new metrics
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"]
               if m["name"] in NEW_METRICS)
    assert len(bench["workloads"]) == 12 \
        and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    line = result_line(run(spec.ROOT, "--workload", CELL, "--seed",
                           "3987654321", "--seconds", "1", "--trace", "1",
                           "--rehearse"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["step.compiles_in_window"]["value"] == 0
    assert all(m["unit"] == "count" for m in line["metrics"].values())
    assert not NEW_METRICS & set(line["metrics"])
    for name in NEW_METRICS:
        reader = spec.load_module("layer_metrics", name)
        assert (reader.UNIT, reader.SOURCE, reader.MOVES, reader.LAYER) \
            == next((m["unit"], m["source"], m["moves"], m["layer"])
                    for m in bench["per_layer"] if m["name"] == name)


def test_the_adapter_stops_at_once_where_the_kind_is_missing(monkeypatch,
                                                             config):
    """On a checkout whose ``hybrid.KINDS`` has no ``G`` (the parent of PR
    58) the family says so and exits: no hang, no traceback."""
    from ray_tpu.models import hybrid

    monkeypatch.setattr(hybrid, "KINDS", {
        k: v for k, v in hybrid.KINDS.items() if k != "G"})
    with pytest.raises(SystemExit, match="no gated-delta-net"):
        spec.load_module("models", "olmo_hybrid").build(config, S)


@pytest.mark.parametrize("key,value", [
    ("linear_allow_neg_eigval", False), ("tie_word_embeddings", True),
    ("attention_bias", True), ("rope_parameters", {"rope_theta": 500000.0}),
    ("linear_num_key_heads", 15)])
def test_the_adapter_refuses_what_the_program_does_not_implement(
        config, key, value):
    with pytest.raises(SystemExit, match=key):
        spec.load_module("models", "olmo_hybrid").build(
            dict(config, **{key: value}), S)
