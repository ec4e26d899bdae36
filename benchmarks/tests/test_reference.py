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
