"""Each plain reference against the program's own ``loss_fn`` at the tiny
preset, on the CPU: loss and every gradient leaf inside the tolerances the
chip run uses (``lib/correct.py``)."""

import jax
import numpy as np
import pytest

from benchmarks.lib import correct, spec
from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh


@pytest.mark.parametrize("preset", ["tiny-llama", "tiny-gpt2"])
def test_reference_agrees_with_the_program(preset):
    config = spec.load_json(spec.BENCH_DIR, "configs", preset + ".json")
    family = spec.load_module("models", config["family"]).build(config, 256)
    params = jax.jit(family.init_fn)(jax.random.key(0))
    mesh = make_mesh(MeshSpec(), jax.local_devices()[:1])
    rows = np.random.default_rng(0).integers(
        0, family.vocab_size, (1, 257)).astype(np.int32)
    tokens, targets = (jax.device_put(a, batch_sharding(mesh))
                       for a in (rows[:, :-1], rows[:, 1:]))
    got = correct.compare(family, params, tokens, targets, mesh)
    assert got["ok"], got
    assert len(got["grad_err_by_leaf"]) == len(jax.tree.leaves(params))
    # Two computations, not one in other clothes: bf16 against float32.
    assert got["grad_err_max"] > 1e-4


def test_query_blocks_do_not_change_the_reference():
    config = spec.load_json(spec.BENCH_DIR, "configs", "tiny-llama.json")
    family = spec.load_module("models", "llama").build(config, 256)
    params = jax.jit(family.init_fn)(jax.random.key(1))
    rows = np.random.default_rng(1).integers(0, 1024, (2, 257)).astype(
        np.int32)
    whole = family.reference_loss(params, rows[:, :-1], rows[:, 1:], 256)
    blocks = family.reference_loss(params, rows[:, :-1], rows[:, 1:], 64)
    assert float(whole) == pytest.approx(float(blocks), rel=1e-6)


def test_recomputing_the_layer_changes_no_bit(monkeypatch):
    """``reference/llama.py`` recomputes each layer in the backward so that
    its gradient fits four chips at 12 layers; the value and every gradient
    leaf are those of the reference that keeps every layer's intermediates."""
    config = spec.load_json(spec.BENCH_DIR, "configs", "tiny-llama.json")
    family = spec.load_module("models", "llama").build(config, 256)
    params = jax.jit(family.init_fn)(jax.random.key(2))
    rows = np.random.default_rng(2).integers(0, 1024, (2, 257)).astype(
        np.int32)

    def value_and_grads():
        return jax.jit(jax.value_and_grad(
            lambda p: family.reference_loss(p, rows[:, :-1], rows[:, 1:],
                                            256)))(params)

    recomputed = value_and_grads()
    monkeypatch.setattr(jax, "checkpoint", lambda layer: layer)
    kept = value_and_grads()
    for a, b in zip(jax.tree.leaves(recomputed), jax.tree.leaves(kept)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mesh_axes, devices", [({}, 1), ({"fsdp": 4}, 4)])
def test_the_control_is_refused(mesh_axes, devices):
    """The reference on weights rounded to 8 bits (``tools/control.py``), in
    the program's place, must come out as not correct at the seed's
    parameters where the program itself passes, on the same rows.  (The
    chip's readings at the cell's own size: PERF.md section 2.)"""
    control = spec.load_module("tools", "control").control
    config = spec.load_json(spec.BENCH_DIR, "configs", "tiny-llama.json")
    limit = config["check"]["seed_grad_tol"]
    family = spec.load_module("models", "llama").build(config, 256)
    mesh = make_mesh(MeshSpec(**mesh_axes), jax.local_devices()[:devices])
    for seed in (0, 1, 2):
        rows = np.random.default_rng(seed).integers(
            0, family.vocab_size, (devices, 257)).astype(np.int32)
        program = correct.at_the_seed(family, mesh, seed, rows, limit)
        refused = correct.at_the_seed(control(family), mesh, seed, rows,
                                      limit)
        assert program["ok"], program
        assert not refused["ok"], refused
        # room on both sides of the limit
        assert 2 * program["grad_norm_err_median"] < limit \
            < refused["grad_norm_err_median"] / 2
