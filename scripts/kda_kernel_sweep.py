#!/usr/bin/env python3
"""One KDA layer's scan alone at the shape ``solar-open2-ep40-tp8`` runs it,
the XLA form (``ops.kda.kda_xla``) against the Pallas kernels
(``ops/kda_kernel.py``): ``chiprun -- python3 scripts/kda_kernel_sweep.py``.

For every variant it first holds the kernels' output and every gradient (q,
k, v, g, beta) against the XLA form's on the same bf16 inputs, then times,
as the mean of ``--calls`` calls a round (the least of ``--rounds`` rounds is
reported beside the mean of all),

- the forward alone;
- forward + backward (``jax.vjp`` pulled back along a fixed cotangent);
- forward + backward under ``jax.checkpoint``, as the layer runs it (the
  forward, the forward again with its residuals, the backward).

The variants: the XLA form; the kernels as ``ops.kda.kda`` runs them (all
the chip's heads a grid step, each chunk's incoming state kept by the
differentiated forward, the triangular system by XLA's forward substitution
in blocks of 16 and joins); the backward running the forward kernel once more
for the states; one, two and four heads a grid step; the triangular system
by all-matmul joins from single positions up (``D - D M D``, six rounds) as
XLA products and as a Pallas kernel that keeps the six rounds in VMEM.  Then
each stage of the taken variant alone (``A`` and ``B``, the inverse in its
three forms and its backward, the cumulative sum, the scan with the outputs),
each kernel by itself.  One JSON
line a measurement goes to ``--out``, a table to stdout.  ``--compile-only``
lowers and compiles every variant for a described v5e on a machine without
one (no times); ``--tiny`` is the rehearsal on the CPU in interpret mode."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import sweep_common as sweep
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu.ops import kda, kda_kernel

#: rows, positions, heads, head_dim, chunk
CELL = (1, 8192, 8, 128, 64)
TINY = (2, 256, 2, 128, 64)
_HI = lax.Precision.HIGHEST


# ------------------------------------------- the inverse by all-matmul joins
def joins_xla(N):
    """``(I + N)^-1`` by joining inverted blocks two by two from single
    positions up, every pair of a width at once: ``D - D M D`` with D the
    blocks inverted so far and M what N holds between the two of a pair."""
    C = N.shape[-1]
    X = np.eye(C, dtype=np.float32) - jnp.where(kda_kernel._pairs(C, 1), N, 0.0)
    w = 2
    while w < C:
        M = jnp.where(kda_kernel._pairs(C, w), N, 0.0)
        X = X - jnp.einsum("...ij,...jk,...kl->...il", X, M, X, precision=_HI)
        w *= 2
    return X


def _joins_kernel(n_ref, x_ref):
    C = n_ref.shape[-1]
    pairs = functools.partial(kda_kernel._pairs, C)
    eye = jnp.where(lax.broadcasted_iota(jnp.int32, (C, C), 0)
                    == lax.broadcasted_iota(jnp.int32, (C, C), 1), 1.0, 0.0)

    def dot(a, b):
        return lax.dot_general(a, b, (((1,), (0,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)

    for j in range(n_ref.shape[0]):
        N = n_ref[j]
        X = eye - jnp.where(pairs(1), N, 0.0)
        w = 2
        while w < C:
            X = X - dot(dot(X, jnp.where(pairs(w), N, 0.0)), X)
            w *= 2
        x_ref[j] = X


@jax.jit
def _joins_pallas(N):
    b, n, H, C, _ = N.shape
    heads = kda_kernel.heads_a_step(H)
    block = pl.BlockSpec((None, None, heads, C, C),
                         lambda i, c, h: (i, c, h, 0, 0))
    return pl.pallas_call(
        _joins_kernel, grid=(b, n, H // heads), in_specs=[block],
        out_specs=block, out_shape=jax.ShapeDtypeStruct(N.shape, N.dtype),
        interpret=jax.default_backend() != "tpu", name="kda_joins")(N)


INVERSES = {
    "forward substitution in 16s and joins, XLA": kda._unit_lower_inverse,
    "all-matmul joins, XLA": joins_xla,
    "all-matmul joins, a kernel": _joins_pallas,
}


def variants(H: int):
    """name -> the scan as f(q, k, v, g, beta, chunk)."""
    taken = kda_kernel.heads_a_step(H)
    out = {"xla": kda.kda_xla,
           f"kernels (taken: {taken} heads a step, states kept, XLA's "
           "substitution)": kda_kernel.scan,
           "kernels, states recomputed": functools.partial(
               kda_kernel.scan, keep_states=False)}
    for heads in (1, 2, 4):
        if heads < taken and H % heads == 0:
            out[f"kernels, {heads} head(s) a step"] = functools.partial(
                kda_kernel.scan, heads=heads)
    for name, inverse in list(INVERSES.items())[1:]:
        out[f"kernels, {name}"] = functools.partial(kda_kernel.scan,
                                                    inverse=inverse)
    return out


def flat(fn, shape):
    """``fn`` over q, k, v, g and o as the mixer holds them, (rows,
    positions, width): the split into heads is a reshape inside the program
    and costs no copy there."""
    b, S, H, d, chunk = shape

    def run(q, k, v, g, beta):
        heads = (b, S, H, d)
        return fn(q.reshape(heads), k.reshape(heads), v.reshape(heads),
                  g.reshape(heads), beta, chunk).reshape(b, S, H * d)

    return run


def stages(shape):
    """name -> (jitted f(inputs, dy), inputs, dy): each stage of the taken
    variant alone, its forward and its backward."""
    b, S, H, d, chunk = shape
    heads, n = kda_kernel.heads_a_step(H), S // chunk
    k = jax.random.split(jax.random.key(7), 8)
    sizes = (chunk, heads, d)

    def wide(i, dt=jnp.bfloat16):
        return (jax.random.normal(k[i], (b, S, H * d)) * 0.1).astype(dt)

    def square(i, dt):
        return jnp.tril(jax.random.normal(
            k[i], (b, n, H, chunk, chunk)) * 0.1, -1).astype(dt)

    G = -jnp.cumsum(jax.random.uniform(k[3], (b, n, chunk, H * d)) * 0.05,
                    axis=2).reshape(b, S, H * d)
    q, key, v, f32 = wide(0), wide(1), wide(2), jnp.float32
    A, T, B = square(4, f32), square(5, jnp.bfloat16), square(6, jnp.bfloat16)
    states = jnp.zeros((b, n, H, d, d), f32)
    out = {
        "A and B, forward": (
            lambda *a: kda_kernel.ab_forward(*a, *sizes), (q, key, G)),
        "A and B, backward": (
            lambda *a: kda_kernel.ab_backward(*a, *sizes),
            (q, key, G, A, B, wide(0, f32), wide(1, f32), wide(2, f32))),
        "scan and outputs, forward": (
            lambda *a: kda_kernel.outputs_forward(*a, *sizes, False),
            (q, key, v, G, T, B)),
        "scan and outputs, forward that keeps the states": (
            lambda *a: kda_kernel.outputs_forward(*a, *sizes, True),
            (q, key, v, G, T, B)),
        "scan and outputs, backward": (
            lambda *a: kda_kernel.outputs_backward(*a, *sizes),
            (q, key, v, G, T, B, states, wide(7))),
        "inverse's backward, XLA": (kda_kernel.inverse_backward, (A, A)),
        "the cumulative sum, XLA": (
            lambda g: kda_kernel.within_chunks(g, chunk), (G,)),
    }
    for name, inverse in INVERSES.items():
        out[f"inverse: {name}"] = (inverse, (A,))
    return {name: (jax.jit(lambda inputs, dy, fn=fn: fn(*inputs)), inputs,
                   None) for name, (fn, inputs) in out.items()}


def inputs(shape, sharding=None):
    b, S, H, d, _ = shape
    wide = ((b, S, H * d), jnp.bfloat16)
    shapes = [wide, wide, wide, ((b, S, H * d), jnp.float32),
              ((b, S, H), jnp.float32), wide]
    if sharding is not None:
        abstract = sweep.abstract(shapes, sharding)
        return tuple(abstract[:5]), abstract[5]
    k = jax.random.split(jax.random.key(45), 6)
    heads = (b, S, H, d)

    def l2norm(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    # what a mixer feeds the scan at the start of training: q and k of unit
    # length a head (q over sqrt(d)), keys that share a part, g = -[1, 16]
    # softplus(...) of a step in [0.001, 0.1], beta around 1
    q = l2norm(jax.random.normal(k[0], heads)) * d ** -0.5
    key = l2norm(jax.random.normal(k[1], heads) + 0.5)
    v = jax.random.normal(k[2], heads)
    g = -jax.random.uniform(k[3], (1, 1, H, 1), minval=1.0, maxval=16.0) \
        * jnp.exp(jax.random.uniform(k[3], heads, minval=np.log(1e-3),
                                     maxval=np.log(0.1)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(k[4], (b, S, H)))
    dy = jax.random.normal(k[5], wide[0], jnp.bfloat16)
    flat = lambda a, t: a.astype(t).reshape(b, S, H * d)  # noqa: E731
    return (flat(q, jnp.bfloat16), flat(key, jnp.bfloat16),
            flat(v, jnp.bfloat16), flat(g, jnp.float32), beta), dy


def main():
    args = sweep.arguments(__doc__, "kda_kernel_sweep")
    shape = TINY if args.tiny else CELL
    sharding = sweep.device(args, f"shape {shape}, ")
    xs, dy = inputs(shape, sharding)
    runs = [(name, which, run, xs, dy)
            for name, fn in variants(shape[2]).items()
            for which, run in sweep.passes(flat(fn, shape)).items()]
    if sharding is None:
        runs += [("stage", name, *rest) for name, rest in
                 stages(shape).items()]
    want = None
    print(f"{'variant':72s} {'pass':20s} {'ms':>9s} {'ms mean':>9s}  "
          "worst leaf against the XLA form (o, dq, dk, dv, dg, dbeta)",
          flush=True)
    with open(args.out, "a") as out:
        for name, which, run, xs, dy in runs:
            row = {"variant": name, "pass": which, "shape": list(shape)}
            try:
                got = sweep.timed(row, run, (xs, dy), args, sharding)
                if got is not None and name != "stage" \
                        and which == "fwd+bwd":
                    if want is None:
                        want = got
                    row["against_xla"] = [round(e, 5)
                                          for e in sweep.close(got, want)]
            except Exception as e:  # a variant the compiler refuses
                row["refused"] = str(e)[-600:]
            sweep.write(out, row)
            print(f"{name:72s} {which:20s} "
                  f"{row.get('ms', row.get('compile_s', -1)):9.3f} "
                  f"{row.get('ms_mean', 0):9.3f}  "
                  f"{row.get('against_xla', row.get('refused', ''))}",
                  flush=True)


if __name__ == "__main__":
    main()
