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

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

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


def passes(fn, shape):
    """name -> jitted f(inputs, dy).  x, B, C and dy come and y goes as the
    layer holds them, (rows, positions, width): the split into heads and
    groups is a reshape inside the program, as in ``models/mamba2.py``, and
    costs no copy there (handed over as (b, S, H, P) the arrays would be
    laid out anew around every variant's call)."""
    b, S, H, P, G, N, _ = shape

    def run(x, delta, A, B, C, D):
        return fn(x.reshape(b, S, H, P), delta, A, B.reshape(b, S, G, N),
                  C.reshape(b, S, G, N), D).reshape(b, S, H * P)

    def pulled(run):
        def both(inputs, dy):
            y, pull = jax.vjp(run, *inputs)
            return y, pull(dy)
        return both

    return {
        "fwd": jax.jit(lambda inputs, dy: run(*inputs)),
        "fwd+bwd": jax.jit(pulled(run)),
        "checkpoint fwd+bwd": jax.jit(pulled(jax.checkpoint(run))),
    }


def inputs(shape, sharding=None):
    b, S, H, P, G, N, _ = shape
    shapes = [((b, S, H * P), jnp.bfloat16), ((b, S, H), jnp.float32),
              ((H,), jnp.float32), ((b, S, G * N), jnp.bfloat16),
              ((b, S, G * N), jnp.bfloat16), ((H,), jnp.float32),
              ((b, S, H * P), jnp.bfloat16)]
    if sharding is not None:
        abstract = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
                    for s, d in shapes]
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


def close(got, want):
    """max |a - b| / max |b| over a pair of pytrees' leaves."""
    return [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32)))
                  / jnp.max(jnp.abs(b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/ssd_kernel_sweep.jsonl")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    shape, sharding = CELL, None
    if args.tiny:
        shape, args.calls, args.rounds = TINY, 1, 1
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
        jax.default_backend = lambda: "tpu"  # the kernels' interpret switch
    elif not args.tiny and jax.default_backend() != "tpu":
        sys.exit("ssd_kernel_sweep: no TPU here (use --tiny or "
                 "--compile-only): a CPU run gives no time")
    device = jax.devices()[0]
    print(f"[sweep] device {device.platform} {device.device_kind}, shape "
          f"{shape}, {args.calls} calls x {args.rounds} rounds", flush=True)
    xs, dy = inputs(shape, sharding)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    want = None
    print(f"{'variant':34s} {'pass':20s} {'ms':>9s} {'ms mean':>9s}  "
          "worst leaf against the XLA form (y, dx, ddelta, dA, dB, dC, dD)",
          flush=True)
    with open(args.out, "a") as out:
        for name, (fn, chunk) in variants(shape[6]).items():
            for which, run in passes(fn, shape).items():
                row = {"variant": name, "pass": which, "shape": list(shape),
                       "chunk": chunk,
                       "grid": [shape[0], shape[4], shape[1] // chunk]}
                t0 = time.perf_counter()
                if sharding is not None:
                    run.lower(xs, dy).compile()
                    row["compile_s"] = round(time.perf_counter() - t0, 2)
                else:
                    got = jax.block_until_ready(run(xs, dy))
                    row["first_call_s"] = round(time.perf_counter() - t0, 2)
                    if which == "fwd+bwd":
                        if want is None:
                            want = got
                        row["against_xla"] = [round(e, 5)
                                              for e in close(got, want)]
                    rounds = []
                    for _ in range(args.rounds):
                        t0 = time.perf_counter()
                        for _ in range(args.calls):
                            got = run(xs, dy)
                        jax.block_until_ready(got)
                        rounds.append((time.perf_counter() - t0)
                                      / args.calls * 1e3)
                    row["ms"] = round(min(rounds), 4)
                    row["ms_mean"] = round(sum(rounds) / len(rounds), 4)
                out.write(json.dumps(row) + "\n")
                out.flush()
                print(f"{name:34s} {which:20s} "
                      f"{row.get('ms', row.get('compile_s')):9.3f} "
                      f"{row.get('ms_mean', 0):9.3f}  "
                      f"{row.get('against_xla', '')}", flush=True)


if __name__ == "__main__":
    main()
