"""What the kernel sweeps share (``splash_block_sweep.py``,
``ssd_kernel_sweep.py``, ``kda_kernel_sweep.py``, ``gmm_tile_sweep.py``,
``rope_pass_sweep.py``, ``mhc_pass_sweep.py``): the
arguments, the device (a chip, a described v5e to compile for, or the CPU's
rehearsal), the three passes a scan is timed in, the comparison with a
reference and the one timing loop.  What each sweeps stays in its file."""

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


def arguments(doc: str, name: str, tiny_calls: int = 1, **more):
    """The sweeps' command line; ``more``: name -> ``add_argument``'s
    keywords.  ``--tiny`` times ``tiny_calls`` calls, one round."""
    ap = argparse.ArgumentParser(description=doc.split("\n\n")[0])
    ap.add_argument("--out", default=f"chiprun_out/{name}.jsonl")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    for flag, keywords in more.items():
        ap.add_argument(flag, **keywords)
    args = ap.parse_args()
    args.name = name
    if args.tiny:
        args.calls, args.rounds = tiny_calls, 1
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    return args


def device(args, what: str = ""):
    """-> the sharding of a described v5e device under ``--compile-only``
    (programs are then compiled for it and not run), else None on a chip or
    under ``--tiny``; a real run without a chip ends here."""
    sharding = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
        jax.default_backend = lambda: "tpu"  # the kernels' interpret switch
    elif not args.tiny and jax.default_backend() != "tpu":
        sys.exit(f"{args.name}: no TPU here (use --tiny or --compile-only): "
                 "a CPU run gives no time")
    d = jax.devices()[0]
    print(f"[sweep] device {d.platform} {d.device_kind}, {what}{args.calls} "
          f"calls x {args.rounds} rounds", flush=True)
    return sharding


def abstract(shapes, sharding):
    """(shape, dtype) pairs as arguments to compile for ``sharding``."""
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]


def passes(run):
    """name -> jitted f(inputs, dy) of a scan ``run(*inputs)``: the forward
    alone, forward + backward (``jax.vjp`` pulled back along ``dy``), and
    both under ``jax.checkpoint``, as a layer runs it."""
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


def close(got, want):
    """max |a - b| / max |b| over a pair of pytrees' leaves."""
    return [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32)))
                  / jnp.max(jnp.abs(b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]


def timed(row: dict, run, operands, args, sharding):
    """Fills ``row``: under a described device's ``sharding`` ``compile_s``
    (``operands`` abstract); else ``first_call_s``, then ``ms``, the least
    of ``--rounds`` rounds' mean over ``--calls`` calls, and ``ms_mean``,
    the mean of all.  -> the first call's result, or None."""
    t0 = time.perf_counter()
    if sharding is not None:
        run.lower(*operands).compile()
        row["compile_s"] = round(time.perf_counter() - t0, 2)
        return None
    first = jax.block_until_ready(run(*operands))
    row["first_call_s"] = round(time.perf_counter() - t0, 2)
    rounds = []
    for _ in range(args.rounds):
        t0 = time.perf_counter()
        for _ in range(args.calls):
            got = run(*operands)
        jax.block_until_ready(got)
        rounds.append((time.perf_counter() - t0) / args.calls * 1e3)
    row["ms"] = round(min(rounds), 4)
    row["ms_mean"] = round(sum(rounds) / len(rounds), 4)
    return first


def write(out, row: dict) -> None:
    out.write(json.dumps(row) + "\n")
    out.flush()
