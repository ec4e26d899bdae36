#!/usr/bin/env python3
"""One Mamba-2 layer's scan alone at the shape ``nemotron-ep16-s8192`` runs
it, the XLA form (``ops.ssd.ssd_xla``) against the Pallas kernels
(``ops/ssd_kernel.py``): ``chiprun -- python3 scripts/ssd_kernel_sweep.py``.

For every variant it first holds the kernels' output and every gradient (x,
delta, A, B, C, D) against the XLA form's on the same bf16 inputs, then
times, as the mean of ``--calls`` calls a round (the least of ``--rounds``
rounds is reported beside the mean of all),

- the forward alone;
- forward + backward (``jax.vjp`` pulled back along a fixed cotangent);
- forward + backward under ``jax.checkpoint``, as the layer runs it (the
  forward, the forward again with its residuals, the backward).

The variants: the XLA form; the kernels with each chunk's incoming state
kept by the differentiated forward (what ``ops.ssd.ssd`` runs); the kernels
with the backward running the forward kernel once more for the states; and
the kernels in chunks of 256 (half the grid steps, four times the (Q x Q)
work a step).  One JSON line a measurement goes to ``--out``, a table to
stdout.  ``--compile-only`` lowers and compiles every variant for a described
v5e on a machine without one (no times); ``--tiny`` is the rehearsal on the
CPU in interpret mode."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import sweep_common as sweep

from ray_tpu.ops import ssd, ssd_kernel

#: rows, positions, heads, head_dim, groups, state, chunk
CELL = (2, 8192, 64, 64, 8, 128, 128)
TINY = (2, 512, 4, 64, 2, 128, 128)


def variants(chunk: int):
    """name -> (the scan as f(x, delta, A, B, C, D), its chunk)."""
    def kernel(keep, chunk):
        return lambda *a: ssd_kernel.scan(*a, chunk, keep)

    return {
        "xla": (lambda *a: ssd.ssd_xla(*a, chunk), chunk),
        "kernel, states kept": (kernel(True, chunk), chunk),
        "kernel, states recomputed": (kernel(False, chunk), chunk),
        "kernel, states kept, chunk x 2": (kernel(True, 2 * chunk),
                                           2 * chunk),
    }


def flat(fn, shape):
    """``fn`` over x, B, C and y as the layer holds them, (rows, positions,
    width): the split into heads and groups is a reshape inside the program,
    as in ``models/mamba2.py``, and costs no copy there (handed over as (b,
    S, H, P) the arrays would be laid out anew around every variant's
    call)."""
    b, S, H, P, G, N, _ = shape

    def run(x, delta, A, B, C, D):
        return fn(x.reshape(b, S, H, P), delta, A, B.reshape(b, S, G, N),
                  C.reshape(b, S, G, N), D).reshape(b, S, H * P)

    return run


def inputs(shape, sharding=None):
    b, S, H, P, G, N, _ = shape
    shapes = [((b, S, H * P), jnp.bfloat16), ((b, S, H), jnp.float32),
              ((H,), jnp.float32), ((b, S, G * N), jnp.bfloat16),
              ((b, S, G * N), jnp.bfloat16), ((H,), jnp.float32),
              ((b, S, H * P), jnp.bfloat16)]
    if sharding is not None:
        abstract = sweep.abstract(shapes, sharding)
        return tuple(abstract[:6]), abstract[6]
    k = jax.random.split(jax.random.key(44), 7)
    x = jax.random.normal(k[0], shapes[0][0], jnp.bfloat16)
    # what a layer feeds the scan at the start of training: delta =
    # softplus(dt + dt_bias) in [0.001, 0.1], A = -[1, 16]
    delta = jnp.exp(jax.random.uniform(
        k[1], shapes[1][0], minval=np.log(1e-3), maxval=np.log(0.1)))
    A = -jax.random.uniform(k[2], (H,), minval=1.0, maxval=16.0)
    B, C = (jax.random.normal(k[i], shapes[3][0], jnp.bfloat16) * 0.5
            for i in (3, 4))
    D = jnp.ones((H,), jnp.float32)
    dy = jax.random.normal(k[6], shapes[6][0], jnp.bfloat16)
    return (x, delta, A, B, C, D), dy


def main():
    args = sweep.arguments(__doc__, "ssd_kernel_sweep")
    shape = TINY if args.tiny else CELL
    sharding = sweep.device(args, f"shape {shape}, ")
    xs, dy = inputs(shape, sharding)
    want = None
    print(f"{'variant':34s} {'pass':20s} {'ms':>9s} {'ms mean':>9s}  "
          "worst leaf against the XLA form (y, dx, ddelta, dA, dB, dC, dD)",
          flush=True)
    with open(args.out, "a") as out:
        for name, (fn, chunk) in variants(shape[6]).items():
            for which, run in sweep.passes(flat(fn, shape)).items():
                row = {"variant": name, "pass": which, "shape": list(shape),
                       "chunk": chunk,
                       "grid": [shape[0], shape[4], shape[1] // chunk]}
                got = sweep.timed(row, run, (xs, dy), args, sharding)
                if got is not None and which == "fwd+bwd":
                    if want is None:
                        want = got
                    row["against_xla"] = [round(e, 5)
                                          for e in sweep.close(got, want)]
                sweep.write(out, row)
                print(f"{name:34s} {which:20s} "
                      f"{row.get('ms', row.get('compile_s')):9.3f} "
                      f"{row.get('ms_mean', 0):9.3f}  "
                      f"{row.get('against_xla', '')}", flush=True)


if __name__ == "__main__":
    main()
