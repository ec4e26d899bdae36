"""The yardstick's mask areas against the masks the program builds: each kind
of ``Family.attention_calls`` charges ``pairs(S)`` a head, and the program's
own attention (``ray_tpu/ops/attention.py``, the einsum path, which builds
its mask from the causal triangle or ``block_diffusion_allowed``) allows just
those pairs, at the convention ``lib/cost.py`` states.

(ISSUE 51 asked for this test in tier 1, ``tests/``; a ``benchmark`` PR may
add no file there, so it stands here until a later PR moves it.)"""

import numpy as np
import pytest

from benchmarks.lib import spec

S = 64


def allowed_by_the_program(config, seq_len):
    """(2S or S, 2S or S) booleans: which keys the program's attention lets
    each query read.  Probed through its entry point: with q = k = 0 every
    allowed key of a query gets the same weight, and with v the identity the
    output row is those weights."""
    import jax.numpy as jnp

    from ray_tpu.ops import attention

    n = 2 * seq_len if "block_length" in config else seq_len
    zeros = jnp.zeros((1, n, 1, 8), jnp.float32)
    v = jnp.eye(n, dtype=jnp.float32)[None, :, None, :]
    if "block_length" in config:
        out = attention.block_diffusion_attention(
            zeros, zeros, v, config["block_length"], "xla")
    else:
        out = attention.causal_attention(zeros, zeros, v, "xla")
    return np.asarray(out[0, :, 0, :]) > 0


@pytest.mark.parametrize("name", [
    c["name"] for c in spec.load_benchmark()["configs"]])
def test_a_kinds_pairs_are_the_pairs_the_programs_mask_allows(name):
    held = spec.load_json(spec.BENCH_DIR, "configs", name + ".json")
    tiny = spec.load_json(spec.BENCH_DIR, "configs",
                          held["rehearse_with"] + ".json")
    calls = spec.load_module("models", tiny["family"]).build(
        tiny, S).attention_calls
    allowed = allowed_by_the_program(tiny, S)
    if "block_length" not in tiny:
        (call,) = calls
        assert (call.q_len, call.kv_len) == (1, 1)
        assert np.array_equal(allowed, np.tril(np.ones((S, S), bool)))
        # the known difference: the yardstick charges half the square, the
        # triangle holds the diagonal's other half too (S / 2 pairs, 0.01 %
        # of the area at S = 8192)
        assert allowed.sum() - call.pairs(S) == S / 2
        return
    whole, noised, clean = calls
    assert (whole.q_len, whole.kv_len) == (2, 2)
    assert allowed.sum() == whole.pairs(S)           # exact: no diagonal rule
    # the two-call cover: the noised copy's queries over all 2S keys, the
    # clean copy's over the clean keys alone
    assert (noised.q_len, noised.kv_len, clean.q_len, clean.kv_len) \
        == (1, 2, 1, 1)
    assert allowed[:S].sum() == noised.pairs(S)
    assert allowed[S:, S:].sum() == clean.pairs(S)
    assert not allowed[S:, :S].any()
    assert allowed[:S, :S].any() and allowed[:S, S:].any()
