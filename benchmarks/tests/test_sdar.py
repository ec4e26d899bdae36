"""Family ``sdar``: the configuration against the catalog's row, the cost
functions' arithmetic, and the adapter's contract with the harness.  (The
program against ``reference/sdar.py`` is tier 1's ``tests/test_sdar.py``.)"""

import jax
import numpy as np
import pytest

from benchmarks.lib import cost, cost_sdar, family, spec

#: the model-configs catalog's row SDAR-30B-A3B-Chat, its ``config``
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


@pytest.fixture(scope="module")
def config():
    return spec.load_json(spec.BENCH_DIR, "configs",
                          "sdar-30b-a3b-l6-ep8.json")


def test_only_the_stated_keys_differ_from_the_source(config):
    differ = {k for k, v in PUBLISHED.items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert config["num_experts_published"] == PUBLISHED["num_experts"]
    first, stop = config["experts_held"]
    assert stop - first == config["num_experts"] == 16
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["mask_token_id"] == config["vocab_size"] - 1
    assert config["eos_token_id"] < config["mask_token_id"]


def test_parameters_are_the_configuration_files_arithmetic(config):
    family = spec.load_module("models", "sdar").build(config, 8192)
    shapes = jax.eval_shape(family.init_fn, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    layer = 18.87e6 + 0.26e6 + 16 * 4.72e6
    assert n == pytest.approx(6 * layer + 2 * 18992 * 2048, rel=2e-3)
    assert n * 14 / 2 ** 30 == pytest.approx(8.42, abs=0.02)  # GiB of state
    assert shapes["blocks"]["router"].shape == (6, 2048, 128)
    assert shapes["blocks"]["w_gate"].shape == (6, 16, 2048, 768)
    assert shapes["blocks"]["wq"].shape == (6, 2048, 32 * 128)
    assert family.vocab_size == 18991 and family.eod_id == 18990


def test_the_parameters_do_not_follow_the_seed(config):
    tiny = spec.load_json(spec.BENCH_DIR, "configs", "tiny-sdar.json")
    drawn = spec.load_module("models", "sdar").build(tiny, 128)
    fixed = spec.load_module("models", "sdar").build(
        dict(tiny, init_seed=34), 128)
    a, b = (fixed.init_fn(jax.random.key(s))["lm_head"] for s in (1, 2))
    c, d = (drawn.init_fn(jax.random.key(s))["lm_head"] for s in (1, 2))
    assert np.array_equal(a, b) and not np.array_equal(c, d)
    assert "init_seed" in config


def test_model_flops_per_token(config):
    S, Bk = 8192, 4
    position = 2 * 2048 * 128 * 36 + 2048 * 128 + 8 * (16 / 128) * 3 \
        * 2048 * 768
    assert cost_sdar.position_matmul_params(config) == position
    want = 6.0 * (2 * 6 * position + 18992 * 2048) \
        + 12.0 * 6 * 4096 * (S + Bk)
    assert cost_sdar.model_flops_per_token(config, S) == want
    # against a causal row of the same widths: twice the layers' matmuls,
    # the head once, twice the attention and a block's width more
    causal = cost.model_flops_per_token(6 * position + 18992 * 2048, 6, 4096,
                                        S)
    assert want - 2 * causal == -6.0 * 18992 * 2048 + 12.0 * 6 * 4096 * Bk


@pytest.mark.parametrize("kind,matmuls,q_like,kv_like", [("fwd", 2, 2, 2),
                                                         ("bwd", 5, 4, 4)])
def test_attention_call_cost(config, kind, matmuls, q_like, kv_like):
    """The one call over a row's 2S x 2S positions (the kind ``whole``)."""
    S = 8192
    whole, noised, clean = spec.load_module("models", "sdar").build(
        config, S).attention_calls
    flops, nbytes = cost.attention_call_cost(kind, 1, whole, S)
    area = S * S + S * 4
    assert flops == matmuls * 2.0 * 32 * area * 128
    assert nbytes == (q_like * 32 + kv_like * 4) * 2 * S * 128 * 2
    # twice a causal call's pairs and a block's width more
    causal, _ = cost.attention_call_cost(kind, 1, family.causal(32, 4, 128),
                                         S)
    assert flops / causal == pytest.approx(2.0, rel=1e-3)
    # the two-call cover does the same work and reads k and v once more
    two = [cost.attention_call_cost(kind, 1, call, S)
           for call in (noised, clean)]
    assert two[0][0] + two[1][0] == flops
    assert two[0][1] + two[1][1] == nbytes + kv_like * 4 * S * 128 * 2
    # compute-bound on the v5e, so the roofline share is FLOPs over time
    assert cost.least_time(flops, nbytes, 197e12, 819e9)[1] == "compute"
