#!/usr/bin/env python3
"""One gated-delta-net layer's scan alone at the shape ``olmo-hybrid-s8192``
runs it (one row of 8192 positions, 30 heads, keys of 96 under values of
192, chunks of 64, bf16), the XLA form (``ops.gdn.gdn_xla``) against the
Pallas kernels (``ops/gdn_kernel.py``): ``chiprun -- python3
scripts/gdn_scan_sweep.py``.

For every variant it first holds the kernels' output and every gradient (q,
k, v, g, beta) against the XLA form's on the same bf16 inputs, then times,
as the mean of ``--calls`` calls a round (the least of ``--rounds`` rounds is
reported beside the mean of all),

- the forward alone;
- forward + backward (``jax.vjp`` pulled back along a fixed cotangent);
- forward + backward under ``jax.checkpoint``, as the layer runs it (the
  forward, the forward again with its residuals, the backward).

The variants are two ways to lay keys of 96 and values of 192 on lane
tiles of 128, each at the heads a grid step its blocks allow:

(i)   the projections' own layout, (rows, positions, H x d), all heads a
      grid step and a head's columns sliced at the lane offset ``96 j``
      (``192 j``): no copy on either side of a call;
(iii) keys zero-padded to 128 in HBM (zeros add nothing to ``K K^T``, ``Q
      K^T`` or the state's products), values as they are or padded to 256,
      in the projections' layout: every slice starts on a tile;

((ii), heads-major operands turned by XLA, read 5.15 / 10.88 / 15.18 ms at
its best against (i)'s 4.11 / 10.31 / 14.24 on the chip at PR 59 and left
``ops/gdn_kernel.py`` with PR 60: ``PERF.md``, PR 59, holds its readings.)

then each stage of (i) alone: ``A``'s kernel and its backward, the scan's
two, XLA's triangular inverse and its backward, the cumulative sum.  One
JSON line a measurement goes to ``--out``, a table to stdout.
``--compile-only`` lowers and compiles every variant for a described v5e on
a machine without one (no times); ``--tiny`` is the rehearsal on the CPU in
interpret mode."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import sweep_common as sweep
from jax import lax

from ray_tpu.ops import gdn, gdn_kernel, kda, kda_kernel

#: rows, positions, heads, a head's keys, its values, chunk
CELL = (1, 8192, 30, 96, 192, 64)
TINY = (2, 256, 6, 96, 192, 64)


def padded(fn, dk_to: int, dv_to: int):
    """``fn`` on keys zero-padded to ``dk_to`` channels a head and values to
    ``dv_to``; the padding and the slices that undo it are timed with it."""
    def pad(x, to):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, to - x.shape[-1]),))

    def run(q, k, v, g, beta, chunk):
        o = fn(pad(q, dk_to), pad(k, dk_to), pad(v, dv_to), g, beta, chunk)
        return o[..., :v.shape[-1]]

    return run


def variants(H: int, dk: int, dv: int):
    """name -> the scan as f(q, k, v, g, beta, chunk)."""
    scan = gdn_kernel.scan
    out = {"xla": gdn.gdn_xla,
           f"(i) the projections' layout, all {H} heads a step (taken)": scan}
    for dv_to in (dv, 2 * gdn_kernel.LANES):
        for heads in (2, 6, H):
            if H % heads == 0:
                out[f"(iii) keys padded to 128, values of {dv_to}, {heads} "
                    "heads a step"] = padded(functools.partial(
                        scan, heads=heads), gdn_kernel.LANES, dv_to)
    return out


def flat(fn, shape):
    """``fn`` over q, k, v and o as the mixer holds them, (rows, positions,
    width): the split into heads is a reshape inside the program and costs
    no copy there."""
    b, S, H, dk, dv, chunk = shape

    def run(q, k, v, g, beta):
        return fn(q.reshape(b, S, H, dk), k.reshape(b, S, H, dk),
                  v.reshape(b, S, H, dv), g, beta, chunk).reshape(
                      b, S, H * dv)

    return run


def stages(shape):
    """name -> (jitted f(inputs, dy), inputs, dy): each stage of the taken
    variant alone, its forward and its backward."""
    b, S, H, dk, dv, chunk = shape
    heads, n = gdn_kernel.heads_a_step(H, dk, dv), S // chunk
    key = jax.random.split(jax.random.key(7), 8)
    sizes = (chunk, heads, H)

    def wide(i, d, dt=jnp.bfloat16):
        return (jax.random.normal(key[i], (b, S, H * d)) * 0.1).astype(dt)

    def square(i, dt):
        return jnp.tril(jax.random.normal(
            key[i], (b, n, H, chunk, chunk)) * 0.1, -1).astype(dt)

    G = -jnp.cumsum(jax.random.uniform(key[3], (b, n, chunk, H)) * 0.05,
                    axis=2)
    cols = gdn_kernel._two_layouts(G, heads)
    q, k, v, f32 = wide(0, dk), wide(1, dk), wide(2, dv), jnp.float32
    A, T = square(4, f32), square(5, jnp.bfloat16)
    states = jnp.zeros((b, n, H, dk, dv), f32)
    out = {
        "A, forward": (
            lambda *a: gdn_kernel.a_forward(*a, *sizes), (k, *cols)),
        "A, backward": (
            lambda *a: gdn_kernel.a_backward(*a, *sizes),
            (k, *cols, A, wide(1, dk, f32))),
        "scan and outputs, forward": (
            lambda *a: gdn_kernel.outputs_forward(*a, *sizes),
            (q, k, v, *cols, T)),
        "scan and outputs, backward": (
            lambda *a: gdn_kernel.outputs_backward(*a, *sizes),
            (q, k, v, *cols, T, states, wide(7, dv))),
        "inverse, XLA": (kda._unit_lower_inverse, (A,)),
        "inverse's backward, XLA": (kda_kernel.inverse_backward, (A, A)),
        "the cumulative sum and its two layouts, XLA": (
            lambda g: gdn_kernel._two_layouts(
                kda_kernel.within_chunks(g, chunk).reshape(G.shape), heads),
            (G.reshape(b, S, H),)),
    }
    return {name: (jax.jit(lambda inputs, dy, fn=fn: fn(*inputs)), inputs,
                   None) for name, (fn, inputs) in out.items()}


def inputs(shape, sharding=None):
    b, S, H, dk, dv, _ = shape
    keys, values = ((b, S, H * dk), jnp.bfloat16), ((b, S, H * dv),
                                                    jnp.bfloat16)
    small = ((b, S, H), jnp.float32)
    shapes = [keys, keys, values, small, small, values]
    if sharding is not None:
        abstract = sweep.abstract(shapes, sharding)
        return tuple(abstract[:5]), abstract[5]
    k = jax.random.split(jax.random.key(59), 6)

    def l2norm(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    # what a mixer feeds the scan at the start of training: q and k of unit
    # length a head (q over sqrt(dk)), keys that share a part, g = -[1, 16]
    # softplus(...) of a step in [0.001, 0.1], beta around 1
    q = l2norm(jax.random.normal(k[0], (b, S, H, dk))) * dk ** -0.5
    key = l2norm(jax.random.normal(k[1], (b, S, H, dk)) + 0.5)
    v = jax.random.normal(k[2], (b, S, H, dv))
    g = -jax.random.uniform(k[3], (1, 1, H), minval=1.0, maxval=16.0) \
        * jnp.exp(jax.random.uniform(k[3], (b, S, H), minval=np.log(1e-3),
                                     maxval=np.log(0.1)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(k[4], (b, S, H)))
    dy = jax.random.normal(k[5], values[0], jnp.bfloat16)
    low = lambda a: a.astype(jnp.bfloat16).reshape(b, S, -1)  # noqa: E731
    return (low(q), low(key), low(v), g, beta), dy


def main():
    args = sweep.arguments(__doc__, "gdn_scan_sweep")
    shape = TINY if args.tiny else CELL
    sharding = sweep.device(args, f"shape {shape}, ")
    xs, dy = inputs(shape, sharding)
    runs = [(name, which, run, xs, dy)
            for name, fn in variants(*shape[2:5]).items()
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
