"""Family ``ouro``: the configuration against the catalog's row, the
parameter count, the cost file's arithmetic (a block and the head four times
a token), the three new readers on a made-up record, the reference against
the program at the rehearsal preset, the 8-bit control, and the cell's
rehearsal.  (The loop against the layers written out, the exit distribution,
the entropy term's reach and the head's per-position form are tier 1's
``tests/test_ouro.py``.)"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import correct, cost, cost_ouro, spec
from benchmarks.tests.test_run import result_line, run
from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh

CELL = "ouro-l8-s4096"
CONFIG = "ouro-2.6b-l8"
FAMILY = "ouro"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"step.exit_gate_ms", "step.ut_loss_gain",
               "step.ut_exit_mass_max"}
SHARED_METRICS = {"step.done_period_ms", "step.done_period_spread",
                  "trainer.starved_dispatches", "step.remat_kept_gib"}
REDUCED = {"num_hidden_layers", "layer_types"}
S, TOKENS = 4096, 8192
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632     # the seven matrices
HEAD = 49152 * 2048


@pytest.fixture(scope="module")
def config():
    return spec.load_json(spec.BENCH_DIR, "configs", CONFIG + ".json")


def _tiny_family(dtype="bfloat16"):
    tiny = spec.load_json(spec.BENCH_DIR, "configs", "tiny-ouro.json")
    tiny["options"] = {"attn_impl": "xla", "dtype": jnp.dtype(dtype),
                       "logits_dtype": jnp.dtype(dtype)}
    return tiny, spec.load_module("models", FAMILY).build(tiny, 128)


def test_only_the_stated_keys_differ_from_the_source(config):
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert config["source"] == row["source_url"]
    published = row["config"]
    differ = {k for k, v in published.items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == REDUCED
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert set(entry["reduced"]) == REDUCED
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] == row["source_url"]
    # every published width, the whole vocabulary, the passes
    assert [config[k] for k in (
        "hidden_size", "head_dim", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "vocab_size",
        "total_ut_steps", "early_exit_threshold", "rms_norm_eps",
        "rope_theta")] == [2048, 128, 5632, 16, 16, 49152, 4, 1, 1e-6, 1e6]
    assert config["num_hidden_layers"] == 8 == len(config["layer_types"])
    assert config["layer_types"] == published["layer_types"][:8]
    assert {"biases", "exit_gate", "next_pass_input", "exit_beta",
            "initialisation", "eos_token_id", "training_dtype", "sizes",
            "lr_warmup_steps"} <= set(config["assumed"])
    assert config["exit_beta"] == 0.1 and config["lr_warmup_steps"] == 500
    assert config["eos_token_id"] < config["vocab_size"]
    assert "six" in config["stands_for"] and config["options"] == {}
    assert config["check"]["seed_grad_tol"] and config["check_why"]
    assert config["rehearse_with"] == "tiny-ouro"


def test_parameters_are_the_issues_arithmetic(config):
    family = spec.load_module("models", FAMILY).build(config, S)
    shapes = jax.eval_shape(family.init_fn, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert LAYER + 4 * 2048 == 51_388_416            # ISSUE 65: 51.4 M
    assert n == 8 * (LAYER + 4 * 2048) + 2 * HEAD + 2048 + 2048 + 1
    assert round(n / 1e6, 1) == 612.4                # ISSUE 65: 612.5
    assert round(n * 10 / 2 ** 30, 2) == 5.70        # GiB of arguments
    assert set(shapes) == {"wte", "blocks", "final_norm", "lm_head",
                           "exit_gate"}
    assert shapes["exit_gate"].shape == (2048 + 1,)
    assert set(shapes["blocks"]) == {
        "attn_norm", "attn_norm_2", "mlp_norm", "mlp_norm_2", "wq", "wk",
        "wv", "wo", "w_gate", "w_up", "w_down"}
    assert shapes["blocks"]["wq"].shape == (8, 2048, 16 * 128)
    assert shapes["blocks"]["w_down"].shape == (8, 5632, 2048)
    assert family.vocab_size == 49152
    (call,) = family.attention_calls
    assert call.shapes(S) == ((16, S, 128),) * 3


def test_model_flops_by_hand(config):
    """A block's matrices, the causal attention, the head and the gate's
    2048 are met four times a token; the embedding is a gather."""
    assert LAYER == 51_380_224
    assert cost.llama_matmul_params(config) == 8 * LAYER + HEAD
    assert cost_ouro.matmul_params_a_pass(config) == 8 * LAYER + HEAD + 2048 \
        == 511_707_136
    assert round(8 * LAYER / 1e6, 1) == 411.0 and round(HEAD / 1e6, 1) == 100.7
    want = 4 * (6.0 * (8 * LAYER + HEAD + 2048) + 6.0 * 8 * S * 2048)
    assert cost_ouro.model_flops_per_token(config, S) == want
    assert want == 13_891_584_000                    # ISSUE 65: 13.9 G
    # four times what lib/cost.py's rule reads for the same stack run once
    once = cost.model_flops_per_token(cost.llama_matmul_params(config), 8,
                                      2048, S)
    assert want == pytest.approx(4 * once, rel=1e-5)
    # the head's share, overstated by the cut: 20 % here, 4 % at 48 layers
    assert round(HEAD / (8 * LAYER + HEAD), 2) == 0.20
    assert round(HEAD / (48 * LAYER + HEAD), 2) == 0.04
    family = spec.load_module("models", FAMILY).build(config, S)
    assert family.flops_per_token == want
    # the one attention call's cost is lib/cost.py's at 16 heads of 128
    (call,) = family.attention_calls
    assert cost.attention_call_cost("fwd", 2, call, S)[0] \
        == 4 * 128 * 2 * 16 * S * S / 2


def _made_up(table, rows=()):
    made = types.SimpleNamespace(profiler_rows=list(rows))
    made.anatomy = {name: tuple(name.split("/")) for name in table}
    made.self_seconds = {name: ms / 1e3 for name, ms in table.items()}
    made.steady = (0.0, 1.0, 1, [1.0])
    made.trace = types.SimpleNamespace(first=types.SimpleNamespace(ops=[]))
    return made


def test_the_new_readers_on_a_made_up_record():
    readers = {name: spec.load_module("layer_metrics", name)
               for name in NEW_METRICS}
    table = {"forward/exit_gate": 0.5, "backward/exit_gate": 1.0,
             "forward/lm_head": 90.0, "backward/attn": 7.0}
    rows = [{"loss_ut": [10.8, 10.8, 10.8, 10.8],
             "ut_exit_mass": [0.5, 0.25, 0.125, 0.125]},
            {"loss_ut": [7.5, 7.25, 7.125, 7.0],
             "ut_exit_mass": [0.25, 0.25, 0.125, 0.375]}]
    made_up = _made_up(table, rows)
    assert readers["step.exit_gate_ms"].read(made_up) == pytest.approx(1.5)
    assert set(readers["step.exit_gate_ms"].describe(made_up)["by_phase"]) \
        == {"forward/exit_gate", "backward/exit_gate"}
    gain = readers["step.ut_loss_gain"]
    assert gain.read(made_up) == pytest.approx(0.5)   # the last row's
    assert gain.describe(made_up) == {
        "rows": 2, "first_row": rows[0]["loss_ut"],
        "last_row": rows[1]["loss_ut"]}
    mass = readers["step.ut_exit_mass_max"]
    assert mass.read(made_up) == 0.5                  # over the window
    assert mass.describe(made_up)["per_pass_max"] == [0.5, 0.25, 0.125, 0.375]
    # a program without the scope and the counters (the parent of PR 65):
    # nothing, no raise
    bare = _made_up({"forward/attn": 7.0, "backward/mlp": 9.0},
                    [{"moe_rows": [[[1]]]}])
    for reader in readers.values():
        assert reader.read(bare) is None
        assert not reader.describe(bare)
    nothing = types.SimpleNamespace(anatomy=None, trace=None, steady=None,
                                    profiler_rows=[])
    assert all(reader.read(nothing) is None for reader in readers.values())


@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    ("float32", 1e-5, 2e-4), ("bfloat16", correct.LOSS_TOL,
                              correct.GRAD_TOL)], ids=["float32", "bfloat16"])
def test_the_program_matches_the_reference_at_the_rehearsal_preset(
        dtype, loss_tol, grad_tol):
    _, family = _tiny_family(dtype)
    params = jax.jit(family.init_fn)(jax.random.key(0))
    # nothing an identity: norms off ones, a gate whose logits reach +-2
    keys = iter(jax.random.split(jax.random.key(7), 5))
    for name in ("attn_norm", "attn_norm_2", "mlp_norm", "mlp_norm_2"):
        params["blocks"][name] = params["blocks"][name] + 0.2 \
            * jax.random.normal(next(keys), params["blocks"][name].shape)
    params["exit_gate"] = 0.1 * jax.random.normal(
        next(keys), params["exit_gate"].shape)
    rows = np.random.default_rng(1).integers(
        0, family.vocab_size, (2, 129)).astype(np.int32)
    tokens, targets = rows[:, :-1], rows[:, 1:]
    loss, grads = jax.jit(jax.value_and_grad(family.loss_fn))(
        params, tokens, targets)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p, t, y: family.reference_loss(p, t, y, 64)))(
        params, tokens, targets)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < loss_tol
    assert grads["exit_gate"].shape == (256 + 1,)
    for path, (a, b) in jax.tree_util.tree_flatten_with_path(
            jax.tree.map(lambda a, b: (a, b), grads, ref_grads),
            is_leaf=lambda x: isinstance(x, tuple))[0]:
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                    / jnp.max(jnp.abs(b)))
        assert err < grad_tol, (jax.tree_util.keystr(path), err)


def test_reference_agrees_with_the_program():
    """``test_reference.py``'s case for the rehearsal preset as the file
    stands, by the comparison the chip run makes (``correct.compare``)."""
    tiny = spec.load_json(spec.BENCH_DIR, "configs", "tiny-ouro.json")
    family = spec.load_module("models", FAMILY).build(tiny, 256)
    params = jax.jit(family.init_fn)(jax.random.key(0))
    mesh = make_mesh(MeshSpec(), jax.local_devices()[:1])
    rows = np.random.default_rng(0).integers(
        0, family.vocab_size, (1, 257)).astype(np.int32)
    tokens, targets = (jax.device_put(a, batch_sharding(mesh))
                       for a in (rows[:, :-1], rows[:, 1:]))
    got = correct.compare(family, params, tokens, targets, mesh)
    assert got["ok"], got
    assert len(got["grad_err_by_leaf"]) == len(jax.tree.leaves(params))
    assert got["grad_err_max"] > 1e-4   # bf16 against float32: two programs


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(spec.BENCH_DIR, "reference", "ouro.py")) as f:
        source = f.read()
    assert "import ray_tpu" not in source and "from ray_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "for _ in range(T)" in source  # the passes, written as a loop


def test_query_blocks_do_not_change_the_reference():
    tiny, family = _tiny_family("float32")
    params = jax.jit(family.init_fn)(jax.random.key(1))
    rows = np.random.default_rng(1).integers(0, 1024, (2, 129)).astype(
        np.int32)
    whole = family.reference_loss(params, rows[:, :-1], rows[:, 1:], 128)
    blocks = family.reference_loss(params, rows[:, :-1], rows[:, 1:], 32)
    assert float(whole) == pytest.approx(float(blocks), rel=1e-6)


@pytest.mark.parametrize("seed", [0, 2])
def test_the_control_is_refused(seed):
    """``tools/control.py``'s control, the reference on weights rounded to 8
    bits, in the program's place at the seed's parameters: refused where the
    program passes, under the rehearsal file's own limit."""
    control = spec.load_module("tools", "control").control
    tiny, family = _tiny_family()
    limit = tiny["check"]["seed_grad_tol"]
    mesh = make_mesh(MeshSpec(), jax.local_devices()[:1])
    rows = np.random.default_rng(seed).integers(
        0, family.vocab_size, (1, 129)).astype(np.int32)
    program = correct.at_the_seed(family, mesh, seed, rows, limit)
    refused = correct.at_the_seed(control(family), mesh, seed, rows, limit)
    assert program["ok"] and program["grad_norm_err_median"] < limit / 2
    assert not refused["ok"] \
        and refused["grad_norm_err_median"] > 2 * limit


def test_the_cell_rehearses_with_every_new_metric():
    """``--rehearse --trace 1`` on the CPU: ``correct`` true, every new
    per-layer metric's reader runs, and the report holds what the two
    counters said (a CPU run prints counts only, so the three metrics, none
    a count, stay out of the line)."""
    bench = spec.load_benchmark()
    cell = spec.load_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "packed-s4096-b2", CONFIG)
    assert {m["name"] for m in cell["metrics"]["per_layer"]
            if m.get("workloads") == [CELL]} == NEW_METRICS
    assert SHARED_METRICS <= {m["name"] for m in cell["metrics"]["per_layer"]
                              if CELL in m.get("workloads", ())}
    seed = "3987654321"
    line = result_line(run(spec.ROOT, "--workload", CELL, "--seed", seed,
                           "--seconds", "1", "--trace", "1", "--rehearse"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["step.compiles_in_window"]["value"] == 0
    assert not NEW_METRICS & set(line["metrics"])
    report = spec.load_json(
        spec.ROOT, "chiprun_out", "benchmarks",
        f"{CELL}.seed{seed}.trace1.rehearse.json")
    notes = report["metric_notes"]
    assert notes["step.ut_loss_gain"]["rows"] == line["attempted"]
    assert len(notes["step.ut_loss_gain"]["last_row"]) == 4
    assert sum(notes["step.ut_exit_mass_max"]["last_row"]) \
        == pytest.approx(1.0, abs=1e-5)
    for name in NEW_METRICS:
        reader = spec.load_module("layer_metrics", name)
        assert (reader.UNIT, reader.SOURCE, reader.MOVES) == next(
            (m["unit"], m["source"], m["moves"]) for m in bench["per_layer"]
            if m["name"] == name)


def test_the_adapter_stops_at_once_where_the_loop_is_missing(monkeypatch,
                                                             config):
    """On a checkout whose ``LlamaConfig`` has no ``ut_steps`` (the parent
    of PR 65) the family says so and exits: no hang, no traceback."""
    import dataclasses

    from ray_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Parents:
        n_layer: int = 8

    monkeypatch.setattr(llama, "LlamaConfig", Parents)
    with pytest.raises(SystemExit, match="runs its layers once"):
        spec.load_module("models", FAMILY).build(config, S)


def test_the_adapter_refuses_what_the_program_does_not_implement(config):
    build = spec.load_module("models", FAMILY).build
    for key, value, says in (
            ("rope_scaling", {"type": "linear", "factor": 2.0},
             "rope_scaling"),
            ("sliding_window", 4096, "sliding_window"),
            ("tie_word_embeddings", True, "tie_word_embeddings"),
            ("layer_types", ["sliding_attention"] * 8, "full attention")):
        with pytest.raises(SystemExit, match=says):
            build(dict(config, **{key: value}), S)
