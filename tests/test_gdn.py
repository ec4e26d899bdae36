"""``ops/gdn.py``, the gated delta rule with one decay a head in chunks,
against the recurrence one position after the other in float32
(``benchmarks/reference/olmo_hybrid.py:recurrence``: no chunk, no cumulative
sum, no triangular system): outputs and all five gradients, over chunks of
16, 64 and the whole row, keys narrower and wider than values, ``beta`` near
2, a decay that sums below -88 inside a chunk, float32 and bfloat16.
Everything on the CPU at small sizes, heads too narrow for the kernels of
``ops/gdn_kernel.py`` (``tests/test_gdn_kernel.py`` has those): every call
here takes the XLA form, through ``gdn`` as a layer calls it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference.olmo_hybrid import l2norm, recurrence
from ray_tpu.ops import gdn as gdn_ops
from ray_tpu.ops import kda as kda_ops
from ray_tpu.ops.gdn import gdn
from tests.families import rel_err

S = 128


def _inputs(case, dk=12, dv=24, b=2, H=3, seed=0):
    """(q, k, v, g, beta) in float32: unit keys, queries scaled by
    dk^-1/2, as the mixer makes them."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = l2norm(jax.random.normal(ks[0], (b, S, H, dk))) * dk ** -0.5
    k = l2norm(jax.random.normal(ks[1], (b, S, H, dk)))
    v = jax.random.normal(ks[2], (b, S, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, S, H)) - 2.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, S, H)))
    if case == "beta-near-2":
        # the reflection's edge: an eigenvalue of I - beta k k^T near -1
        beta = 2.0 - 1e-3 * jax.random.uniform(ks[4], (b, S, H))
    if case == "strong-decay":
        # 6 to 10 a position: -96 to -160 inside a chunk of 16, under -88
        g = -(6.0 + 4.0 * jax.random.uniform(ks[3], (b, S, H)))
    return q, k, v, g, beta


def _both(chunk, dtype, args):
    """((o, the five gradients) by the chunked form on ``dtype`` operands,
    the same by the recurrence in float32), under one probe."""
    probe = jax.random.normal(jax.random.key(9), args[2].shape)

    assert gdn_ops.path(args[0].shape, args[2].shape, chunk,
                        jax.sharding.get_abstract_mesh()) == "xla"

    def ours(q, k, v, g, beta):
        o = gdn(q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta,
                chunk)
        assert o.dtype == dtype and o.shape == v.shape
        return jnp.sum(o.astype(jnp.float32) * probe), o

    def theirs(*args):
        o = recurrence(*args)
        return jnp.sum(o * probe), o

    with jax.default_matmul_precision("highest"):
        return tuple(
            jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                       has_aux=True))(*args)
            for f in (ours, theirs))


@pytest.mark.parametrize("case", ["plain", "beta-near-2", "strong-decay"])
@pytest.mark.parametrize("dtype,tol", [
    # the same mathematics in another order: float32 summation order, the
    # triangular system's inverse in place of 127 dependent steps
    ("float32", 2e-4),
    # operands and T rounded to 8 bits of mantissa, float32 accumulation:
    # a few times 2^-8, as ops/kda.py's tests hold its bf16 form
    ("bfloat16", 4e-2)], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [16, 64, S], ids=["c16", "c64", "row"])
def test_outputs_and_gradients_are_the_recurrences(chunk, dtype, tol, case):
    args = _inputs(case)
    ((_, o), grads), ((_, want), want_grads) = _both(
        chunk, jnp.dtype(dtype), args)
    assert np.all(np.isfinite(np.asarray(o, np.float32)))
    assert rel_err(o, want) < tol
    for name, got, ref in zip(("q", "k", "v", "g", "beta"), grads,
                              want_grads):
        assert np.all(np.isfinite(np.asarray(got))), name
        assert rel_err(got, ref) < tol, (name, rel_err(got, ref))


@pytest.mark.parametrize("dk,dv", [(12, 24), (24, 8), (16, 16)])
def test_keys_and_values_of_unlike_widths(dk, dv):
    """The state is dk x dv: keys narrower than values (the published 96
    under 192), wider, and alike."""
    args = _inputs("plain", dk=dk, dv=dv, seed=1)
    ((_, o), grads), ((_, want), want_grads) = _both(32, jnp.float32, args)
    assert o.shape == (2, S, 3, dv)
    assert rel_err(o, want) < 2e-4
    assert all(rel_err(a, b) < 2e-4 for a, b in zip(grads, want_grads))


def test_a_decay_that_underflows_neither_overflows_nor_leaks():
    """With g near -40 a position every decay between two positions
    underflows to zero: each output is its own position's ``beta (q.k) v``
    and nothing else, with no inf or NaN in value or gradient; the
    factorised form e^{G_t} e^{-G_s} would overflow at the third position."""
    q, k, v, _, beta = _inputs("plain")
    g = jnp.full(beta.shape, -40.0)
    o, pull = jax.vjp(lambda *a: gdn(*a, 64), q, k, v, g, beta)
    own = beta[..., None] * jnp.sum(q * k, axis=-1, keepdims=True) * v
    assert rel_err(o, own) < 1e-5
    assert all(np.all(np.isfinite(np.asarray(a)))
               for a in pull(jnp.ones_like(o)))


def test_a_row_starts_from_a_zero_state_and_reads_no_later_position():
    q, k, v, g, beta = _inputs("plain")
    o = gdn(q, k, v, g, beta, 16)
    # rows are independent: the second row alone gives the second row
    alone = gdn(*(a[1:] for a in (q, k, v, g, beta)), 16)
    assert rel_err(o[1:], alone) < 1e-6
    # a change at position 40 reaches positions 40 and later of its row
    moved = gdn(q, k, v.at[0, 40].add(1.0), g, beta, 16)
    changed = np.any(np.asarray(moved != o), axis=(2, 3))
    assert not changed[0, :40].any() and changed[0, 40:].all()
    assert not changed[1].any()


@pytest.mark.parametrize("chunk", [24, 256])
def test_a_chunk_that_does_not_fit_is_refused(chunk):
    with pytest.raises(ValueError, match="power of"):
        gdn(*_inputs("plain"), chunk)


def test_the_inverse_is_kdas_and_not_a_copy():
    assert gdn_ops._unit_lower_inverse is kda_ops._unit_lower_inverse
    source = open(gdn_ops.__file__).read()
    assert "def _unit_lower_inverse" not in source
    assert "pallas" not in source  # the kernels are ops/gdn_kernel.py's
    from ray_tpu.ops import gdn_kernel
    assert gdn_kernel._unit_lower_inverse is kda_ops._unit_lower_inverse
