#!/usr/bin/env python3
"""The grouped products of an expert layer alone (``ops/grouped_matmul.py``)
at the shapes the five cells with experts hand them, over candidate (rows,
contraction, columns) tiles: ``chiprun --timeout 3000 -- python3
scripts/gmm_tile_sweep.py``.

A cell's layer makes two kinds of product over the rows of one window:
``up`` (rows x hidden -> the expert's width; gate too, where experts have
one) and ``down`` (back).  A candidate is (row tile, the tile of the hidden
width, the tile of the expert's width); each product's forward, dx and dW
walk it with their own roles, as ``grouped_matmul._bwd`` hands them on.  For
every cell, every pattern of group sizes and every candidate the sweep

1. **first holds the wrapper's forward, dx and dW against the dense product
   group by group** (each held group's rows times its matrix, bf16 operands,
   float32 sums; ``sweep_common.close``: the largest deviation over the
   largest element), and the rows of groups not held against zero: a
   candidate over ``--tolerance`` (bf16's rounding is 0.004) is *wrong*, is
   not timed and is named in the table;
2. then times each of the six calls alone (``gmm``, the transposed ``gmm``
   and ``tgmm`` of ``up`` and of ``down``), the mean of ``--calls`` calls a
   round, the least of ``--rounds`` rounds, and adds them up as one layer
   runs them (gate and up twice each where the layer recomputes them).

The group sizes: ``cell``, the held rows and the largest group over the mean
one that the ledger's lines give for the cell (``step.moe_held_rows``,
``step.moe_load_max``; PR 48), the rest of the window in the group behind
the held run; and ``even``, the whole window spread evenly over the held
groups.  The first candidate of a cell is the parent's tile (512, 1024,
1024), the one marked ``*`` what ``grouped_matmul.tile_for`` returns.  One
JSON line a candidate goes to ``--out``, a table to stdout.
``--compile-only`` lowers and compiles every call for a described v5e on a
machine without one (no times: what the compiler refuses for VMEM shows
here); ``--tiny`` is the rehearsal on the CPU in interpret mode."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import sweep_common as sweep

from ray_tpu.ops import grouped_matmul as gm

#: cell -> (window rows, hidden, expert width, groups, held, first held,
#: matrices into the width (gate and up: 2), held rows, largest / mean)
CELLS = {
    "solar-open2-ep40-tp8": (8192, 4096, 1280, 10, 8, 1, 2, 3309, 4.09),
    "nemotron-ep16-s8192": (12288, 2688, 1856, 10, 8, 1, 1, 4251, 4.0),
    "sdar-ep8-s8192": (16384, 2048, 768, 18, 16, 1, 2, 15129, 10.98),
    "joyai-ep16-s8192": (8192, 2048, 768, 18, 16, 1, 2, 3202, 11.15),
    "olmoe-s4096": (65536, 2048, 1024, 64, 64, 0, 2, 65536, 6.03),
}
TINY = {"tiny": (1024, 640, 384, 6, 4, 1, 2, 300, 2.0)}
PARENT = (512, 1024, 1024)
#: beside the parent's and the rule's: (row tile, hidden's, width's)
CANDIDATES = {
    "solar-open2-ep40-tp8": [
        (512, 1024, 1280), (512, 512, 1280), (256, 1024, 640),
        (256, 1024, 1280), (1024, 1024, 640), (128, 1024, 640),
        (512, 1024, 256), (512, 2048, 640)],
    "nemotron-ep16-s8192": [
        (256, 896, 640), (1024, 896, 640), (512, 896, 1024),
        (512, 1024, 640), (512, 384, 640), (512, 896, 384)],
    "sdar-ep8-s8192": [
        (256, 1024, 768), (1024, 1024, 768), (512, 512, 768),
        (512, 2048, 768), (512, 1024, 384)],
    "joyai-ep16-s8192": [
        (256, 1024, 768), (128, 1024, 768), (1024, 1024, 768),
        (512, 2048, 768)],
    "olmoe-s4096": [
        (256, 1024, 1024), (1024, 1024, 1024), (512, 512, 1024),
        (512, 1024, 512)],
    "tiny": [(128, 256, 128), (256, 640, 384)],
}


def group_sizes(cell, pattern: str) -> np.ndarray:
    """(groups,) int: ``even``: the window over the held groups alike;
    ``cell``: the held rows over the held groups with weights r^i, r such
    that the largest is ``load`` times the mean, in an order drawn once;
    what the window has left lies in the group behind the held run."""
    m, _, _, groups, held, first, _, rows, load = cell
    if pattern == "even":
        sizes = np.full(held, m // held)
        sizes[:m - sizes.sum()] += 1
    else:
        lo, hi = 1.0, 1e6
        for _ in range(200):  # the largest of r^i over their mean rises in r
            r = (lo * hi) ** 0.5
            w = r ** -np.arange(held, dtype=np.float64)
            lo, hi = (r, hi) if w.max() / w.mean() < load else (lo, r)
        sizes = np.floor(min(rows, m) * w / w.sum()).astype(np.int64)
        sizes = np.random.default_rng(50).permutation(sizes)
    out = np.zeros(groups, np.int64)
    out[first:first + held] = sizes
    out[-1] += m - out.sum()  # behind the held run, or the last group's
    return out


@jax.jit
def dense(lhs, rhs, dout, starts):
    """The three products group by group: each held group's rows (the others
    zeroed) times its matrix, bf16 operands, float32 sums.  ``starts``:
    (held + 1,) the held groups' first rows and the run's end."""
    rows = jnp.arange(lhs.shape[0])[:, None]

    def one(carry, group):
        out, dlhs = carry
        w, lo, hi = group
        mine = (rows >= lo) & (rows < hi)
        a, d = jnp.where(mine, lhs, 0), jnp.where(mine, dout, 0)
        f32 = dict(preferred_element_type=jnp.float32)
        return (out + jnp.dot(a, w, **f32), dlhs + jnp.dot(d, w.T, **f32)), \
            jnp.dot(a.T, d, **f32).astype(rhs.dtype)

    zeros = (jnp.zeros(dout.shape, jnp.float32),
             jnp.zeros(lhs.shape, jnp.float32))
    (out, dlhs), drhs = jax.lax.scan(one, zeros,
                                     (rhs, starts[:-1], starts[1:]))
    return out.astype(lhs.dtype), dlhs.astype(lhs.dtype), drhs


def calls(first):
    """name -> jitted f(lhs, rhs, dout, sizes): the wrapper's forward alone,
    dx alone and dW alone (the compiler drops the calls nobody reads)."""
    def product(a, b, sizes):
        return gm.grouped_matmul(a, b, sizes, first)

    return {
        "gmm": jax.jit(lambda a, b, d, s: product(a, b, s)),
        "gmm^T": jax.jit(lambda a, b, d, s: jax.vjp(
            lambda a: product(a, b, s), a)[1](d)[0]),
        "tgmm": jax.jit(lambda a, b, d, s: jax.vjp(
            lambda b: product(a, b, s), b)[1](d)[0]),
    }


def main():
    args = sweep.arguments(
        __doc__, "gmm_tile_sweep", tiny_calls=1,
        **{"--cells": dict(nargs="*"),
           "--tolerance": dict(type=float, default=0.01)})
    cells = TINY if args.tiny else {
        name: CELLS[name] for name in args.cells or CELLS}
    sharding = sweep.device(args)
    rule = gm.tile_for
    dtype = jnp.float32 if args.tiny else jnp.bfloat16
    print(f"{'cell':22s} {'sizes':5s} {'tile (rows, hidden, width)':28s} "
          f"{'right':>7s} {'worst':>8s} | up: gmm gmm^T tgmm | down: gmm "
          "gmm^T tgmm | a layer, ms", flush=True)
    with open(args.out, "a") as out:
        for name, cell in cells.items():
            m, hidden, width, groups, held, first, n_in, _, _ = cell
            ruled = rule(m, hidden, width)
            assert ruled[1:] == rule(m, width, hidden)[:0:-1], \
                "the rule gives a width one tile whatever its role"
            candidates = list(dict.fromkeys(
                [PARENT, ruled] + CANDIDATES["tiny" if args.tiny else name]))
            # up / down -> lhs, rhs, dout: abstract under --compile-only
            operands = {}
            for which, (k, n) in (("up", (hidden, width)),
                                  ("down", (width, hidden))):
                shapes = [((m, k), dtype), ((held, k, n), dtype),
                          ((m, n), dtype)]
                if sharding is not None:
                    operands[which] = sweep.abstract(shapes, sharding)
                    continue
                keys = jax.random.split(jax.random.key(50 + k), 3)
                operands[which] = [
                    (jax.random.normal(key, shape, jnp.float32)
                     * (k ** -0.5 if len(shape) == 3 else 1.0)).astype(dtype)
                    for key, (shape, _) in zip(keys, shapes)]
            # pattern -> the sizes, the held run's bounds, what is wanted
            patterns = {}
            for pattern in ("cell", "even")[:1 if sharding else 2]:
                sizes = group_sizes(cell, pattern)
                starts = np.concatenate(
                    [[0], np.cumsum(sizes)])[first:first + held + 1]
                if sharding is not None:
                    patterns[pattern] = (sizes, sweep.abstract(
                        [((groups,), jnp.int32)], sharding)[0], starts, None)
                    continue
                patterns[pattern] = (
                    sizes, jnp.asarray(sizes, jnp.int32), starts,
                    {which: dense(*ops, jnp.asarray(starts, jnp.int32))
                     for which, ops in operands.items()})
            for tile in candidates:
                tm, t_hidden, t_width = tile
                if m % tm:
                    continue
                asked = []

                def candidate(m_, k_, n_):
                    asked.append((m_, k_, n_))
                    widths = {hidden: t_hidden, width: t_width}
                    return (tm, min(widths[k_], k_), min(widths[n_], n_))

                runs = calls(first)
                for pattern, (sizes, on_device, starts, want) in \
                        patterns.items():
                    row = {"cell": name, "sizes": pattern,
                           "tile": list(tile), "rule": tile == ruled,
                           "held_rows": int(starts[-1] - starts[0]),
                           "largest_group": int(np.diff(starts).max())}
                    gm.tile_for = candidate
                    try:
                        measure(row, runs, operands, on_device, starts, want,
                                args, sharding)
                    except Exception as e:  # the compiler's refusal, mostly
                        row["refused"] = str(e).splitlines()[0][:300]
                    finally:
                        gm.tile_for = rule
                    assert asked, "the wrapper never asked for a tile"
                    if "ms" in row:
                        ms = row["ms"]
                        row["layer_ms"] = round(
                            n_in * (2 * ms["up.gmm"] + ms["up.gmm^T"]
                                    + ms["up.tgmm"]) + ms["down.gmm"]
                            + ms["down.gmm^T"] + ms["down.tgmm"], 4)
                    sweep.write(out, row)
                    report(row)


def measure(row, runs, operands, sizes, starts, want, args, sharding):
    """Fills ``row``: ``worst`` (the six calls' deviations from the dense
    products; rows of groups not held that are not zero read inf),
    ``right``, and, where right, ``ms`` of each call."""
    row["worst"], row["right"] = {}, True
    for which in operands if want is not None else ():
        for (kind, run), wanted in zip(runs.items(), want[which]):
            got = run(*operands[which], sizes)
            err = sweep.close(got, wanted)[0]
            if kind != "tgmm" and bool(jnp.any(got[:starts[0]] != 0)
                                       | jnp.any(got[starts[-1]:] != 0)):
                err = float("inf")
            row["worst"][f"{which}.{kind}"] = round(err, 5)
            row["right"] &= err < args.tolerance
    if not row["right"]:
        return
    row["ms" if sharding is None else "compile_s"] = timings = {}
    for which, ops in operands.items():
        for kind, run in runs.items():
            one = {}
            sweep.timed(one, run, (*ops, sizes), args, sharding)
            timings[f"{which}.{kind}"] = one.get("ms", one.get("compile_s"))


def report(row):
    timings = row.get("ms") or row.get("compile_s") or {}
    worst = max(row.get("worst", {}).values(), default=float("nan"))
    print(f"{row['cell']:22s} {row['sizes']:5s} "
          f"{str(tuple(row['tile'])) + (' *' if row['rule'] else ''):28s} "
          f"{'refused' if 'refused' in row else str(row['right']):>7s} "
          f"{worst:8.5f} | "
          + " ".join(f"{timings.get(f'up.{k}', float('nan')):6.3f}"
                     for k in ("gmm", "gmm^T", "tgmm")) + " | "
          + " ".join(f"{timings.get(f'down.{k}', float('nan')):6.3f}"
                     for k in ("gmm", "gmm^T", "tgmm"))
          + f" | {row.get('layer_ms', float('nan')):7.3f}"
          + (f"  {row['refused']}" if "refused" in row else ""), flush=True)


if __name__ == "__main__":
    main()
