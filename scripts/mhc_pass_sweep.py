#!/usr/bin/env python3
"""The four passes of a sub-layer's hyper-connections alone, XLA's form of
``models/streams.py`` beside ``ops/streams_kernel.py``'s Mosaic passes, at
``xing4-ep8-s4096``'s shape ((8192, 3584) x 4 streams in bf16): ``chiprun --
python3 scripts/mhc_pass_sweep.py``.

The passes, with the arrays of (tokens, C) each must move (``need ms``: their
bytes at the chip's 819 GB/s; ``share``: that over the time taken):

- ``maps+read``: ``X`` -> ``H``, ``u`` (n + 1);
- ``write``: ``X``, ``y``, ``H`` -> ``X'`` (2n + 1);
- ``write bwd``: the cotangent of ``X'``, ``X``, ``y``, ``H`` -> the
  cotangents of ``y`` and ``H`` and the stream map's share of ``X``'s (3n + 2);
- ``read bwd``: the cotangents of ``u`` and ``H``, that share, ``X`` ->
  the cotangents of ``X``, ``phi``, ``alpha`` and ``base`` (3n + 1; XLA's
  form adds the share outside, the kernel's in the pass), behind the
  forward's pass that a ``jax.vjp`` of it runs for what the backward reads
  (``H``; n + 1 more, as a layer's checkpoint runs it): 4n + 2;
- ``sub-layer``: all of it around a branch that is one multiplication, forward
  and backward under ``jax.checkpoint`` as a step runs it (46 arrays and the
  branch's 4).

Candidates: ``xla``, and ``kernel <positions>`` at each row block the rule
admits (``streams_kernel.BLOCKS``; ``*`` the one ``block`` picks), each at
every ``--unroll`` (lane tiles a turn of the kernels' loop over a row's
lanes; the module's own where none is given).  Every
kernel candidate is first held against ``xla`` by the largest difference over
the largest value (``off``).  One JSON line a candidate goes to ``--out``, a
table to stdout.  ``--compile-only`` compiles every candidate for a described
v5e on a machine without one (no times); ``--tiny`` is the rehearsal on the
CPU in interpret mode."""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
from jax import lax
import sweep_common as sweep

from ray_tpu.models import streams  # noqa: E402
from ray_tpu.ops import streams_kernel  # noqa: E402

PEAK_BYTES_S = 819e9
BF16, F32 = jnp.bfloat16, jnp.float32
N = 4


def config(C: int):
    return types.SimpleNamespace(
        streams=N, d_model=C, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_clamp=(-10.0, 10.0), rms_eps=1e-6)


#: pass -> the arrays of (tokens, C) it must move
ARRAYS = {"maps+read": N + 1, "write": 2 * N + 1, "write bwd": 3 * N + 2,
          "read bwd": 4 * N + 2, "sub-layer": 50}


def forms(c, kernel: bool):
    """-> f(X, y, hc, H, G, dH) -> pass -> its function of nothing."""
    def maps_read(X, hc):
        if kernel:
            return streams_kernel.maps_read(X, hc, c)
        H, _ = streams.maps(X, hc, c)
        return H, streams.read(X, H), X

    write = streams_kernel.write if kernel else streams.write

    def sublayer(X, hc):
        X = lax.optimization_barrier(X)
        H, u, X = maps_read(X, hc)
        H, u = lax.optimization_barrier((H, u))
        y = lax.optimization_barrier(u * jnp.asarray(0.5, u.dtype))
        return lax.optimization_barrier(write(X, y, H))

    def passes(X, y, hc, H, G, dH):
        def both():
            out, pull = jax.vjp(jax.checkpoint(sublayer), X, hc)
            return out, pull(G)

        return {
            "maps+read": lambda: maps_read(X, hc)[:2],
            "write": lambda: write(X, y, H),
            "write bwd": lambda: jax.vjp(write, X, y, H)[1](G),
            "read bwd": lambda: jax.vjp(maps_read, X, hc)[1]((dH, y, G)),
            "sub-layer": both,
        }

    return passes


def drawn(shape, dtype, key, scale=1.0):
    return (jax.random.normal(jax.random.key(key), shape, F32)
            * scale).astype(dtype)


def main():
    args = sweep.arguments(
        __doc__, "mhc_pass_sweep", tiny_calls=1,
        **{"--unroll": dict(nargs="*", type=int,
                            default=[streams_kernel.UNROLL])})
    B, S, C = (2, 128, 256) if args.tiny else (2, 4096, 3584)
    sharding = sweep.device(args, f"({B * S}, {C}) x {N} streams, ")
    if args.tiny:
        streams_kernel.on_chip = lambda: True
    c = config(C)
    shapes = {"X": tuple(((B, S, C), BF16) for _ in range(N)),
              "y": ((B, S, C), BF16),
              "hc": {"phi": ((N * C, 24), F32), "alpha": ((3,), F32),
                     "base": ((24,), F32)},
              "H": ((B, S, 24), F32), "dH": ((B, S, 24), F32)}
    is_pair = lambda a: isinstance(a, tuple) and not isinstance(a[1], tuple)
    if sharding is not None:
        ops = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            *a, sharding=sharding), shapes, is_leaf=is_pair)
    else:
        hc = jax.tree.map(lambda a: a[0], streams.init_params(
            c, jax.random.key(7), 1))
        hc = {"phi": hc["phi"] * 5, "alpha": hc["alpha"] * 100,
              "base": hc["base"] * 0.1}
        X = tuple(drawn((B, S, C), BF16, 10 + j) for j in range(N))
        ops = {"X": X, "y": drawn((B, S, C), BF16, 20), "hc": hc,
               "H": jax.jit(lambda X, hc: streams.maps(X, hc, c)[0])(X, hc),
               "dH": drawn((B, S, 24), F32, 21, 0.1)}
    ops["G"] = ops["X"] if sharding is not None else tuple(
        drawn((B, S, C), BF16, 30 + j) for j in range(N))
    tokens = B * S
    rule = streams_kernel.block(tokens, C, N, 2)
    cands = [("xla", None, None)] + [
        (f"kernel {rows}" + ("*" if rows == rule else "")
         + (f" u{unroll}" if len(args.unroll) > 1 else ""), rows, unroll)
        for rows in streams_kernel.BLOCKS if tokens % rows == 0
        for unroll in args.unroll]
    print(f"{'pass':10s} {'candidate':15s} {'off':>8s} {'ms':>8s} "
          f"{'need ms':>8s} {'share':>6s}", flush=True)
    with open(args.out, "a") as out:
        for name, arrays in ARRAYS.items():
            want = None
            for label, rows, unroll in cands:
                if rows:  # the kernels' jits know neither by their keys
                    streams_kernel.BLOCKS = (rows,)
                    streams_kernel.UNROLL = unroll
                    jax.clear_caches()
                passes = forms(c, rows is not None)

                def run(X, y, hc, H, G, dH, passes=passes):
                    return passes(X, y, hc, H, G, dH)[name]()

                need = arrays * tokens * C * 2 / PEAK_BYTES_S * 1e3
                row = {"pass": name, "candidate": label,
                       "need_ms": round(need, 4)}
                try:
                    got = sweep.timed(
                        row, jax.jit(run),
                        [ops[k] for k in ("X", "y", "hc", "H", "G", "dH")],
                        args, sharding)
                except Exception as e:  # what the compiler refuses
                    row["error"] = str(e)[:400]
                    got = None
                if got is not None:
                    if want is None:
                        want = got
                    else:
                        row["off"] = max(sweep.close(got, want))
                sweep.write(out, row)
                ms = row.get("ms")
                print(f"{name:10s} {label:15s} "
                      f"{row.get('off', float('nan')):8.1e} "
                      f"{ms if ms is not None else float('nan'):8.3f} "
                      f"{need:8.3f} "
                      f"{100 * need / ms if ms else float('nan'):5.1f}% "
                      f"{row.get('error', row.get('compile_s', ''))}",
                      flush=True)


if __name__ == "__main__":
    main()
