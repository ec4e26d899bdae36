#!/usr/bin/env python3
"""A window's return to its tokens alone (``models/moe.py:_from_window``) at
the six held-share cells' shapes and at the held rows a layer their ledger
lines read (``step.moe_held_rows``, PR 56): ``chiprun -- python3
scripts/moe_return_sweep.py``.

A shape is a layer's (N tokens, k experts a token, width D, H held of E
experts) and the rows its router sends the held experts; the routing is drawn
so that the held experts get that many (a selection bias on the held experts'
scores, found by bisection).  Two windows of it are timed: ``first``, the
run's first (full where the layer holds more rows than a window has), and
``last``, the one that holds what is left.  The forms:

- ``gather``: today's, the gather of all k x N slots, written out as (k, N,
  D), and the masked float32 sum of the k slabs;
- ``kernel <tokens>x<columns>/<chunk>``: ``ops/window_return.py``, the sort
  and the gather of the window's R rows into token order and the Mosaic call
  at a candidate (tokens a tile, columns a block, rows a chunk); the one
  marked ``*`` is what ``window_return.tile`` returns;
- ``kernel part``: the rule's kernel in two parts: ``sort+rows`` (the
  tokens' sort and the gather of R rows into their order), ``call`` (the
  items' lists and the Mosaic call, on rows already sorted);
- ``scatter-add``: ``zeros.at[tokens].add(rows)`` in float32 with the indices
  sorted and said to be, then one rounding.

Every form is first held against a float32 scatter-add on the host (``err``:
the largest deviation in units of half a bf16 step of the wanted value; over
one is *wrong*, is not timed and is named).  Then ``--layers`` independent
layers (each its own routing) are timed a call, the mean of ``--calls`` calls
a round, the least of ``--rounds`` rounds, a layer; ``x bytes`` is the time
over what the form's own bytes need at the chip's 819 GB/s, and ``least`` the
time of the bytes the work has (the window's R rows read once, the N tokens
written once).  One JSON line a form goes to ``--out``, a table to stdout.
``--compile-only`` compiles every form for a described v5e (no times: what
the compiler refuses shows here); ``--tiny`` is the rehearsal on the CPU in
interpret mode."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import sweep_common as sweep

from ray_tpu.models import moe  # noqa: E402
from ray_tpu.ops import window_return  # noqa: E402

#: cell -> (N, k, D, H held, E experts, held rows a layer: ledger, PR 56)
SHAPES = {
    "lfm2-ep4-s8192": (16384, 4, 2048, 8, 32, 16988),
    "solar-open2-ep40-tp8": (8192, 8, 4096, 8, 320, 3493),
    "sdar-ep8-s8192": (16384, 8, 2048, 16, 128, 16381),
    "nemotron-ep16-s8192": (16384, 6, 2688, 8, 128, 4141),
    "laguna-ep32-s8192": (8192, 10, 3072, 8, 256, 3704),
    "joyai-ep16-s8192": (8192, 8, 2048, 16, 256, 3249),
}
TINY = {"tiny k4": (512, 4, 256, 4, 16, 700),
        "tiny k6": (256, 6, 128, 2, 12, 100)}
#: beside the rule's: (tokens a tile, columns a block or None for the
#: rule's, rows a chunk)
TILES = [(128, None, 128), (64, None, 128), (512, None, 128),
         (256, None, 256), (128, None, 256), (128, None, 64),
         (128, 4096, 128), (256, 512, 128)]
PEAK_BYTES_S = 819e9


def routing(N, k, E, H, held, seed):
    """-> (N, k) expert ids, the k largest of Gumbel scores, the first H
    experts' raised by the bias under which they get ``held`` pairs."""
    rng = np.random.default_rng(seed)
    scores = rng.gumbel(size=(N, E)).astype(np.float32)

    def chosen(bias):
        s = scores.copy()
        s[:, :H] += bias
        return np.argpartition(-s, k - 1, axis=1)[:, :k]

    lo, hi = -20.0, 20.0
    for _ in range(30):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if (chosen(mid) < H).sum() < held else (lo, mid)
    return chosen(hi).astype(np.int32)


def window(experts, E, H, which, D, seed, abstract=None):
    """-> (rows, pairs, inverse, run) of the ``which`` window (``first`` /
    ``last``) of the held run, as ``moe._move_window`` cuts it; the rows of
    the window outside the run NaN."""
    N, k = experts.shape
    order, inverse, sizes = (np.asarray(a) for a in moe.sort_pairs(
        jnp.asarray(experts), E))
    R, total = moe.window_rows(N * k), int(sizes[:H].sum())
    c = 0 if which == "first" else (total - 1) // R
    first = c * R
    stop, lead = min(first + R, total), max(first + R - N * k, 0)
    pairs = order[first - lead:first - lead + R]
    rows = np.random.default_rng(seed).standard_normal(
        (R, D)).astype(np.float32)
    at = np.arange(R)
    rows[~((at >= lead) & (at < lead + stop - first))] = np.nan
    return (jnp.asarray(rows, jnp.bfloat16), jnp.asarray(pairs),
            jnp.asarray(inverse),
            tuple(jnp.int32(v) for v in (first, stop, lead)))


def reference(rows, pairs, inverse, run):
    """The float32 scatter-add on the host.  -> (N, D) float32."""
    first, stop, lead = (int(v) for v in run)
    rows = np.asarray(rows, np.float32)
    N, k = inverse.shape
    own = slice(lead, lead + stop - first)
    out = np.zeros((N, rows.shape[1]), np.float32)
    np.add.at(out, np.asarray(pairs)[own] // k, rows[own])
    return out


def scatter_add(rows, pairs, inverse, run):
    N = inverse.shape[0]
    tokens, source = window_return.by_token(pairs, inverse, run)
    picked = jnp.where((tokens < N)[:, None], rows[source], 0)
    return jnp.zeros((N, rows.shape[1]), jnp.float32).at[tokens].add(
        picked.astype(jnp.float32), indices_are_sorted=True,
        mode="drop").astype(rows.dtype)


def parts(R, N, D):
    """name -> f(rows, pairs, inverse, run) of one part of the rule's
    kernel form, and the bytes it needs."""
    T, block = window_return.tile(R, N, D)

    def gathered(rows, pairs, inverse, run):
        # (a gather by constant indices would be another program, so the
        # sort is timed with it)
        return rows[window_return.by_token(pairs, inverse, run)[1]]

    def call(rows, pairs, inverse, run):
        first, stop, lead = run
        at = jax.lax.iota(jnp.int32, R)
        inside = (at >= lead) & (at < lead + (stop - first))
        # expert order is not token order: the call alone is timed on the
        # tokens of rows that rise (the values are then not the return's)
        tokens = jax.lax.sort(jnp.where(
            inside, (at - lead) * N // jnp.maximum(stop - first, 1), N))
        return window_return.call(
            rows, tokens, (stop - first)[None], N, T, block,
            window_return.CHUNK, jax.default_backend() != "tpu")

    return {"sort+rows": (gathered, 4 * R * D),
            "call": (call, 2 * (R + N) * D)}


def forms(R, N, k, D, tiny):
    """name -> (f(rows, pairs, inverse, run), what ``window_return`` is to
    hold meanwhile: (on_chip, tile, chunk) or None, the bytes the form needs,
    whether its values are the return's)."""
    def program(*window):
        return moe._from_window(*window)

    out = {"gather": (program, (False, None, None),
                      2 * (2 * k * N + N) * D, True)}
    ruled = window_return.tile(R, N, D)
    if ruled is None:
        return out
    tried = dict.fromkeys(
        [(*ruled, window_return.CHUNK)]
        + [(T, block or ruled[1], P)
           for T, block, P in TILES
           if N % T == 0 and R % P == 0 and D % (block or 128) == 0])
    kernel_bytes = 2 * (3 * R + N) * D
    for T, block, P in list(tried)[:2] if tiny else tried:
        star = " *" if (T, block, P) == (*ruled, window_return.CHUNK) else ""
        out[f"kernel {T}x{block}/{P}{star}"] = (
            program, (True, (T, block), P), kernel_bytes, True)
    for name, (f, need) in parts(R, N, D).items():
        out[f"kernel part {name}"] = (f, None, need, False)
    out["scatter-add"] = (scatter_add, None, 2 * (3 * R + N) * D
                          + 8 * (R + 2 * N) * D, True)
    return out


def main():
    args = sweep.arguments(
        __doc__, "moe_return_sweep", tiny_calls=1,
        **{"--shapes": dict(nargs="*"),
           "--layers": dict(type=int, default=4)})
    shapes = TINY if args.tiny else {
        name: SHAPES[name] for name in args.shapes or SHAPES}
    sharding = sweep.device(args)
    kept = (window_return.on_chip, window_return.tile, window_return.CHUNK)
    print(f"{'shape':22s} {'window':6s} {'rows in':>7s} {'form':28s} "
          f"{'err':>6s} | {'ms':>7s} {'x bytes':>7s} {'least ms':>8s}",
          flush=True)
    with open(args.out, "a") as out:
        for name, (N, k, D, H, E, held) in shapes.items():
            layers = 1 if args.tiny else args.layers
            routed = [routing(N, k, E, H, held, 57 + i)
                      for i in range(layers)]
            R = moe.window_rows(N * k)
            for which in ("first", "last"):
                windows = [window(experts, E, H, which, D, 157 + i)
                           for i, experts in enumerate(routed)]
                inside = int(windows[0][3][1] - windows[0][3][0])
                if which == "last" and inside == min(R, held):
                    continue  # one window: the first is the last
                want = reference(*windows[0])
                operands = windows
                if sharding is not None:
                    operands = jax.tree.map(
                        lambda a: jax.ShapeDtypeStruct(
                            a.shape, a.dtype, sharding=sharding), windows)
                for label, (f, held_as, need, returns) in forms(
                        R, N, k, D, args.tiny).items():
                    if held_as:
                        chip, tile, chunk = held_as
                        window_return.on_chip = lambda chip=chip: chip
                        if tile:
                            window_return.tile = lambda *_, t=tile: t
                            window_return.CHUNK = chunk
                    row = {"shape": name, "window": which, "inside": inside,
                           "form": label, "layers": layers,
                           "need_ms": round(need / PEAK_BYTES_S * 1e3, 4),
                           "least_ms": round(2 * (R + N) * D / PEAK_BYTES_S
                                             * 1e3, 4)}
                    try:
                        if sharding is None and returns:
                            got = np.asarray(
                                jax.jit(lambda *w: f(*w))(*windows[0]),
                                np.float32)
                            row["err"] = float(np.max(
                                np.abs(got - want) / np.maximum(
                                    2.0 ** -8 * np.abs(want), 1e-30))
                            ) if np.all(np.isfinite(got)) else float("inf")
                        if row.get("err", 0) <= 1:
                            one = {}
                            sweep.timed(one, jax.jit(lambda ws, f=f: [
                                f(*w) for w in ws]), (operands,), args,
                                sharding)
                            if "ms" in one:
                                row["ms"] = round(one["ms"] / layers, 4)
                            row.update({n: v for n, v in one.items()
                                        if n != "ms"})
                    except Exception as e:  # the compiler's refusal, mostly
                        row["refused"] = str(e).splitlines()[0][:300]
                    finally:
                        (window_return.on_chip, window_return.tile,
                         window_return.CHUNK) = kept
                    sweep.write(out, row)
                    report(row)


def report(row):
    ms = row.get("ms")
    timed = (f"{ms:7.3f} {ms / row['need_ms']:7.2f}" if ms and row["need_ms"]
             else f"{ms:7.3f} {'':7s}" if ms
             else f"{row.get('compile_s', float('nan')):6.2f}s {'':7s}")
    print(f"{row['shape']:22s} {row['window']:6s} {row['inside']:7d} "
          f"{row['form']:28s} {row.get('err', float('nan')):6.2f} | {timed} "
          f"{row['least_ms']:8.3f}"
          + (f"  {row['refused'][:140]}" if "refused" in row else ""),
          flush=True)


if __name__ == "__main__":
    main()
