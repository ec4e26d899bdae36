#!/usr/bin/env python3
"""The rotary pass of a layer alone (``models/layers.py:rope`` over q and k)
at the shapes the cells hand it, every candidate **with its delivery into the
layout the splash call reads** (q scaled by ``hd ** -0.5`` and both handed
over head-major, as ``ops/attention.py:splash_attention`` takes them; the
backward from head-major cotangents to what the projections' transposes
read): ``chiprun -- python3 scripts/rope_pass_sweep.py``.

The candidates of a shape:

- ``product``: :func:`layers._rope_pass` as it stands, the partner lanes by a
  product with a 0/1 permutation at ``Precision.HIGHEST`` (the parent's pass);
- ``product default``: the same product at the default precision (one bf16
  pass of the matrix unit; exact either way where x is bf16);
- ``kernel <block>x<groups>``: ``ops/rope_kernel.py``, one Mosaic call for q
  and k that writes head-major and scales q on its way out (``rope``'s
  ``scales``), at candidate (positions a grid step, groups the heads are
  walked in); the one marked ``*`` is what ``rope_kernel.blocks`` returns;
- ``kernel rows <block>x<groups>``: the same kernel writing (B, S, H x hd)
  unscaled, XLA scaling and transposing after it;
- ``kernel <block>x<groups> scale after``: the rule's, q's scale left to
  XLA: after a Mosaic call a pass of its own over q.

Every candidate is first held against the float32 sliced formula
(``tests/test_rope_kernel.py:sliced``, ``tests/test_llama.py:_sliced_rope``
with the partial, scaled and copied cases) and autodiff through it, values
and gradient: ``differ`` counts the elements that are not bit-equal and
``ulp`` is the largest deviation in units of the wanted value's last place
(``last_place``: a bf16's, and of 2^-10's under it); a candidate over one is
*wrong*, is not timed and is named (``delivered`` counts the elements of the
scaled q that differ from the ``product``'s: the compiler may keep or drop the
rounding between the pass and the scale).  Then the forward
and the backward are timed apart, ``--layers`` independent layers a call (a
call of one is the host's dispatch, 0.35 ms, whatever it holds), the mean of
``--calls`` calls a round, the least of ``--rounds`` rounds, a layer; ``x bytes`` is the time over what the bytes of
the pass need at the chip's 819 GB/s (q and k read once and written once, the
scale and the delivery inside the pass).  One JSON line a
candidate goes to ``--out``, a table to stdout.  ``--compile-only`` lowers and
compiles every candidate for a described v5e on a machine without one (no
times: what the compiler refuses shows here; ``--hlo DIR`` keeps the compiled
texts); ``--tiny`` is the rehearsal on the CPU in interpret mode."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import sweep_common as sweep

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))

from test_rope_kernel import last_place, sliced  # noqa: E402

from ray_tpu.models import layers  # noqa: E402
from ray_tpu.ops import rope_kernel  # noqa: E402

THETA = 10000.0
YARN = layers.Yarn(factor=32.0, original=4096)
#: shape -> (B, S, the arrays' heads, hd, rope's keywords)
SHAPES = {
    "laguna-window 72+8": (1, 8192, (72, 8), 128, {}),
    "laguna-full 48+8": (1, 8192, (48, 8), 128, dict(
        rotary=64, first=True, scale=YARN.scale,
        inv_freq=YARN.inv_freq(64, THETA))),
    "mistral-s8192 32+8": (1, 8192, (32, 8), 128, {}),
    "mistral-s1024 32+8": (8, 1024, (32, 8), 128, {}),
    "mistral-fsdp4 32+8": (1, 4096, (32, 8), 128, {}),
    "olmoe 16+16": (2, 4096, (16, 16), 128, {}),
    "sdar 32+4 x2": (1, 16384, (32, 4), 128, dict(copies=2)),
    "joyai 32 of 192": (1, 8192, (32,), 192, dict(rotary=64,
                                                  interleave=True)),
}
TINY = {
    "tiny 6+2": (2, 128, (6, 2), 128, {}),
    "tiny 4+2 x2": (1, 256, (4, 2), 128, dict(rotary=64, first=True,
                                              copies=2)),
    "tiny 3 of 48": (1, 128, (3,), 48, dict(rotary=16, interleave=True)),
}
#: beside the rule's: (positions a grid step, groups of heads)
BLOCKS = [(128, 1), (256, 1), (64, 1), (256, 4), (1024, 8)]
PEAK_BYTES_S = 819e9


def product_default(x, hd, theta=THETA, rotary=None, interleave=False,
                    first=False, inv_freq=None, scale=1.0, copies=1):
    """``layers.rope``'s product an array at the default precision, with its
    backward as the pass with the sine negated."""
    def one(direction):
        def turn(a):
            B, S, H = *a.shape[:2], a.shape[2] // hd
            a = a.reshape(B * copies, S // copies, H, hd)
            cos, sin, lane, partner = layers._rope_tables(
                S // copies, hd, theta, direction, rotary, interleave, first,
                inv_freq, scale)
            swap = (lane[:, None] == partner(lane[None, :])).astype(a.dtype)
            swapped = jnp.einsum("bshd,de->bshe", a, swap,
                                 preferred_element_type=jnp.float32)
            return (a.astype(jnp.float32) * cos + swapped * sin
                    ).astype(a.dtype).reshape(B, S, H * hd)
        return lambda xs: tuple(turn(a) for a in xs)

    return tuple(a.reshape(*a.shape[:2], -1, hd)
                 for a in _with_its_backward(one)(x))


def _with_its_backward(one):
    """-> f(xs) = ``one(1.0)(xs)`` whose backward is ``one(-1.0)``."""
    turned = jax.custom_vjp(one(1.0))
    turned.defvjp(lambda xs: (one(1.0)(xs), None),
                  lambda _, gs: (one(-1.0)(tuple(gs)),))
    return turned


def kernel_rows(block, groups):
    """-> rope(xs, **how) by the kernel writing (B, S, H x hd), both ways."""
    def rope(xs, hd, theta=THETA, rotary=None, first=False, inv_freq=None,
             scale=1.0, copies=1):
        def one(direction):
            def turn(xs):
                S = xs[0].shape[1]
                rot = hd if rotary is None else rotary
                cos, sin, _, _ = layers._rope_tables(
                    S // copies, hd, theta, direction, rotary, False, first,
                    inv_freq, scale)
                return tuple(rope_kernel.call(
                    cos.reshape(-1, hd), sin.reshape(-1, hd), xs,
                    half=rot // 2, before=0 if first else hd - rot,
                    head_major_in=False, head_major_out=False, block=block,
                    groups=groups))
            return turn

        return tuple(a.reshape(*a.shape[:2], -1, hd)
                     for a in _with_its_backward(one)(xs))
    return rope


def delivered(rope, how, folds):
    """-> f(xs): q and k, (B, S, H x hd) as the projections write them,
    rotated, q scaled (by ``rope`` itself where it ``folds`` the scale) and
    both handed over as the splash call reads them."""
    scale = how["hd"] ** -0.5

    def f(xs):
        if folds:
            q, *rest = rope(xs, scales=(scale,) + (None,) * (len(xs) - 1),
                            **how)
        else:
            q, *rest = rope(xs, **how)
            q = q * scale
        return tuple(a.transpose(0, 2, 1, 3) for a in (q, *rest))
    return f


def candidates(heads, hd, how, positions, tiny):
    """name -> (rope(xs, **how), the blocks ``rope_kernel.blocks`` is to
    return meanwhile or None, whether ``rope`` takes ``scales``)."""
    def program(xs, **kw):
        return layers.rope(xs, THETA, **kw)  # ``hd`` among them

    out = {"product": (program, None, True),
           "product default": (product_default, None, False)}
    if hd % rope_kernel.LANES or how.get("interleave"):
        return out
    ruled = rope_kernel.blocks(positions, heads, hd, 2)
    tried = [(b, g) for b, g in dict.fromkeys([ruled] + BLOCKS)
             if positions % b == 0 and all(H % g == 0 for H in heads)]
    for blocks in tried[:2] if tiny else tried:
        out["kernel {}x{}".format(*blocks)
            + (" *" if blocks == ruled else "")] = (program, blocks, True)
    out["kernel rows {}x{}".format(*ruled)] = (kernel_rows(*ruled), None,
                                                False)
    out["kernel {}x{} scale after".format(*ruled)] = (program, ruled, False)
    return out


def ulps(got, want):
    """-> (elements that differ, the largest deviation in units of the
    wanted value's last place) over a pair of tuples of arrays."""
    differ, worst = 0, 0.0
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        differ += int((a != b).sum())
        worst = max(worst, float((np.abs(a - b) / last_place(b)).max()))
    return differ, worst


def main():
    args = sweep.arguments(
        __doc__, "rope_pass_sweep", tiny_calls=1,
        **{"--shapes": dict(nargs="*"), "--hlo": dict(default=None),
           "--layers": dict(type=int, default=4)})
    shapes = TINY if args.tiny else {
        name: SHAPES[name] for name in args.shapes or SHAPES}
    sharding = sweep.device(args)
    on_chip, rule = rope_kernel.on_chip, rope_kernel.blocks
    print(f"{'shape':20s} {'candidate':26s} {'right':>7s} {'differ':>9s} "
          f"{'ulp':>5s} | fwd ms (x bytes)  bwd ms (x bytes)", flush=True)
    with open(args.out, "a") as out:
        for name, (B, S, heads, hd, how) in shapes.items():
            positions = S // how.get("copies", 1)
            layout = [((B, S, H * hd), jnp.bfloat16) for H in heads]
            handed = [((B, H, S, hd), jnp.bfloat16) for H in heads]
            need = sum(2 * 2 * B * S * H * hd for H in heads)
            if sharding is not None:
                xs, gs, want = (tuple(sweep.abstract(layout, sharding)),
                                tuple(sweep.abstract(handed, sharding)), None)
            else:
                def drawn(key, shapes):
                    return tuple(
                        jax.random.normal(k, s, jnp.float32).astype(d)
                        for k, (s, d) in zip(jax.random.split(
                            key, len(shapes)), shapes))

                xs, gs = (drawn(jax.random.key(53 + i), shapes)
                          for i, shapes in enumerate((layout, handed)))
                want = held(lambda xs, hd, **kw: tuple(
                    sliced(a.reshape(B, S, -1, hd), THETA, **kw)
                    for a in xs), dict(how, hd=hd))(xs, gs)
            # the layers of a timed call: the first's arrays and L - 1 more
            layers_xs, layers_gs = ([first] + [
                first if sharding is not None else jax.tree.map(
                    lambda a, i=i: a * (1 + i), first)
                for i in range(1, args.layers)] for first in (xs, gs))
            base = None  # the product's delivery of the first layer
            for label, (rope, blocks, folds) in candidates(
                    heads, hd, how, positions, args.tiny).items():
                # the program's rule takes the kernel where it can: the
                # product is a candidate only with the rule's hand held
                rope_kernel.on_chip = lambda: label.startswith("kernel")
                if blocks:
                    rope_kernel.blocks = lambda *_: blocks
                f = delivered(rope, dict(how, hd=hd), folds)
                runs = {"fwd": jax.jit(lambda xss, gss: [f(xs) for xs in xss]),
                        "bwd": jax.jit(lambda xss, gss: [
                            jax.vjp(f, xs)[1](gs)[0]
                            for xs, gs in zip(xss, gss)])}
                row = {"shape": name, "candidate": label,
                       "need_ms": round(need / PEAK_BYTES_S * 1e3, 4)}
                try:
                    if sharding is None:
                        handed = jax.jit(f)(xs)
                        base = handed if base is None else base
                        row["differ_delivered"] = ulps(handed[:1], base[:1])
                    measure(row, runs, held(rope, dict(how, hd=hd)),
                            layers_xs, layers_gs, want, args, sharding)
                except Exception as e:  # the compiler's refusal, mostly
                    row["refused"] = str(e).splitlines()[0][:300]
                finally:
                    rope_kernel.on_chip, rope_kernel.blocks = on_chip, rule
                sweep.write(out, row)
                report(row)


def held(rope, how):
    """-> f(xs, gs): (values, gradient) of the pass alone, before the
    delivery (whose second rounding the compiler may or may not keep), the
    cotangents turned back from head-major."""
    @jax.jit
    def f(xs, gs):
        out, pull = jax.vjp(lambda xs: rope(xs, **how), xs)
        return out, pull(tuple(g.transpose(0, 2, 1, 3) for g in gs))[0]
    return f


def measure(row, runs, check, xss, gss, want, args, sharding):
    """Fills ``row``: ``differ`` and ``ulp`` of values and gradient (the
    first layer's) against the sliced formula, ``right`` and, where right,
    each pass's ``ms`` a layer (or ``compile_s``)."""
    row["right"] = True
    if want is not None:
        for which, g, w in zip(("fwd", "bwd"), check(xss[0], gss[0]), want):
            differ, worst = ulps(g, w)
            row[f"differ_{which}"], row[f"ulp_{which}"] = differ, worst
            row["right"] &= worst <= 1.0
    if not row["right"]:
        return
    for which, run in runs.items():
        one = {}
        if sharding is not None and args.hlo:
            os.makedirs(args.hlo, exist_ok=True)
            text = run.lower(xss, gss).compile().as_text()
            tag = f"{row['shape']}.{row['candidate']}.{which}".replace(" ", "_")
            with open(os.path.join(args.hlo, tag + ".hlo.txt"), "w") as f:
                f.write(text)
        sweep.timed(one, run, (xss, gss), args, sharding)
        if sharding is None:
            row[f"ms_{which}"] = round(one["ms"] / len(xss), 4)
        else:
            row[f"compile_s_{which}"] = one["compile_s"]


def report(row):
    def cell(which):
        ms = row.get(f"ms_{which}")
        if ms is None:
            return f"{row.get(f'compile_s_{which}', float('nan')):7.2f} s"
        return f"{ms:7.3f} ({ms / row['need_ms']:5.2f})"

    differ = row.get("differ_fwd", 0) + row.get("differ_bwd", 0)
    worst = max(row.get("ulp_fwd", 0.0), row.get("ulp_bwd", 0.0))
    print(f"{row['shape']:20s} "
          f"{row['candidate']:26s} "
          f"{'refused' if 'refused' in row else str(row['right']):>7s} "
          f"{differ:9d} {worst:5.2f} | {cell('fwd')}  {cell('bwd')}"
          f"  delivered {row.get('differ_delivered', '')}"
          + (f"  {row['refused'][:120]}" if "refused" in row else ""),
          flush=True)


if __name__ == "__main__":
    main()
