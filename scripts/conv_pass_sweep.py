#!/usr/bin/env python3
"""The short causal convolution of the four kinds that run
``mamba2.causal_conv`` (`M`, `K`, `G`, `C`), XLA's form against
``ops/conv_kernel.py``'s Mosaic pass, at the four cells' shapes: ``chiprun --
python3 scripts/conv_pass_sweep.py``.

A shape's candidates, each timed forward alone and forward + backward (the
gradients of x, the taps and the bias pulled back along a drawn dy):

- ``xla``: the call site's expression as it stands off the chip
  (``silu(causal_conv(...)).astype(dt)``, ``gated_conv``), the span sliced
  out of the projection's output where the call site slices
  (``tests/test_conv_kernel.py:forms``, the tests' oracle);
- ``kernel <positions>x<lanes>``: the kernel at candidate blocks; the one
  marked ``*`` is what ``conv_kernel.blocks`` returns.

Every kernel candidate is first held against ``xla``: values and every
gradient, by the largest difference over the largest value (``off``).
``need ms`` is what the bytes of the pass cost at the chip's 819 GB/s: x read
and the result written forward; x and dy read and dx written backward.

Then **inside one layer** (``--layers``, on by default): the kind's ``mixer``
of the cell's configuration, forward + backward under ``jax.checkpoint`` as a
step runs it, with the rule's hand held at ``xla`` and free at ``kernel``.

One JSON line a candidate goes to ``--out``, a table to stdout.
``--compile-only`` compiles every candidate for a described v5e on a machine
without one (no times: what the compiler refuses shows here); ``--tiny`` is
the rehearsal on the CPU in interpret mode."""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import sweep_common as sweep

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))

from test_conv_kernel import forms  # noqa: E402

from ray_tpu.models import hybrid  # noqa: E402
from ray_tpu.ops import conv_kernel  # noqa: E402

PEAK_BYTES_S = 819e9
ROOT = os.path.join(os.path.dirname(__file__), "..")
BF16, F32 = jnp.bfloat16, jnp.float32

#: name -> (kind, its cell's configuration and family module, rows, S, the
#: array's width, the span's offset, the widths that leave, taps, bias, the
#: result's dtype, gated)
SHAPES = {
    "nemotron-xBC": ("M", "nemotron-3-nano-30b-a3b-l9-ep16", "nemotron_h",
                     2, 8192, 10304, 4096, (4096, 1024, 1024), 4, True, BF16,
                     False),
    "solar-qk": ("K", "solar-open2-250b-l4-ep40-tp8", "solar_open2",
                 1, 8192, 1024, 0, (1024,), 4, False, F32, False),
    "solar-v": ("K", None, None, 1, 8192, 1024, 0, (1024,), 4, False, BF16,
                False),
    "olmo-qk": ("G", "olmo-hybrid-7b-l4", "olmo_hybrid",
                1, 8192, 2880, 0, (2880,), 4, False, F32, False),
    "olmo-v": ("G", None, None, 1, 8192, 5760, 0, (5760,), 4, False, BF16,
               False),
    "lfm2-gate": ("C", "lfm2-8b-a1b-l7-ep4", "lfm2_moe",
                  2, 8192, 6144, 0, (2048,), 3, False, BF16, True),
}
TINY = {
    "tiny-xBC": ("M", None, None, 2, 64, 640, 128, (256, 128), 4, True, BF16,
                 False),
    "tiny-qk": ("G", None, None, 1, 64, 160, 0, (160,), 4, False, F32, False),
    "tiny-gate": ("C", None, None, 2, 64, 768, 0, (256,), 3, False, BF16,
                  True),
}
#: candidate (positions, lanes) a step beside the rule's
BLOCKS = [(256, 512), (512, 512), (1024, 512), (2048, 512), (1024, 256),
          (512, 1024)]
GATED_BLOCKS = [(64, 512), (128, 512), (256, 512), (128, 256), (128, 1024),
                (128, 2048)]


def one_layer(kind: str, config_name: str, family: str, rows: int, S: int):
    """-> (f(x, blk) of the kind's mixer under a checkpoint, pulled back
    along x itself; x and one layer of the kind's parameters as shapes)."""
    import importlib

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config_name + ".json")) as f:
        c = json.load(f)
    sys.path.insert(0, ROOT)
    _, config = importlib.import_module(
        "benchmarks.models." + family).model_config(c, S)
    module = hybrid.KINDS[kind].module
    axes = module.logical_axes(config)
    blk = jax.eval_shape(lambda: jax.tree.map(
        lambda a: a[0], module.init_params(config, jax.random.key(0), 1,
                                           0.02)))
    x = jax.ShapeDtypeStruct((rows, S, config.d_model), config.dtype)

    def run(x, blk):
        out, pull = jax.vjp(
            jax.checkpoint(lambda x, blk: module.mixer(x, blk, config, axes)),
            x, blk)
        return out, pull(x)

    return run, (x, blk)


def row_major(tree, sharding):
    """``tree``'s arrays of three axes -> a row-major format each, the
    others None: as a step's projections write them.  (Left to itself XLA
    lays a parameter whose last axis is no multiple of 128, as 10304 or
    2880, out positions-minor and copies it before a Mosaic call: a pass
    that no step has.)"""
    from jax.experimental.layout import Format, Layout

    return jax.tree.map(
        lambda a: Format(Layout(major_to_minor=(0, 1, 2)), sharding)
        if a.ndim == 3 else None, tree)


def passes(run, operands, sharding):
    """name -> ``run``'s forward alone and its forward + backward, jitted
    over ``operands`` ((inputs, dy)) in row-major formats."""
    def both(inputs, dy):
        y, pull = jax.vjp(run, *inputs)
        return y, pull(dy)

    fns = {"fwd": lambda inputs, dy: run(*inputs), "fwd+bwd": both}
    return {name: jax.jit(
        fn, in_shardings=row_major(operands, sharding),
        out_shardings=row_major(jax.eval_shape(fn, *operands), sharding))
        for name, fn in fns.items()}


def drawn(tree, key):
    leaves, treedef = jax.tree.flatten(tree)
    return treedef.unflatten([
        (jax.random.normal(k, a.shape, F32) * 0.5).astype(a.dtype)
        for k, a in zip(jax.random.split(key, len(leaves)), leaves)])


def main():
    args = sweep.arguments(
        __doc__, "conv_pass_sweep", tiny_calls=1,
        **{"--shapes": dict(nargs="*"),
           "--no-layers": dict(action="store_true")})
    shapes = TINY if args.tiny else {
        name: SHAPES[name] for name in args.shapes or SHAPES}
    sharding = sweep.device(args)
    on_chip, rule = conv_kernel.on_chip, conv_kernel.blocks
    print(f"{'shape':14s} {'candidate':20s} {'off':>8s} | fwd ms (need)  "
          f"fwd+bwd ms (need)", flush=True)
    with open(args.out, "a") as out:
        for name, (kind, config_name, family, rows, S, full, offset, widths,
                   taps, bias, out_dtype, gated) in shapes.items():
            span = full // 3 if gated else sum(widths)
            abstract = [((rows, S, full), BF16), ((taps, span), F32)] \
                + ([((span,), F32)] if bias else [])
            dys = [((rows, S, w), out_dtype) for w in widths]
            if sharding is not None:
                inputs = sweep.abstract(abstract, sharding)
                dy = tuple(sweep.abstract(dys, sharding))
            else:
                inputs = drawn([jax.ShapeDtypeStruct(s, d)
                                for s, d in abstract], jax.random.key(61))
                dy = tuple(drawn([jax.ShapeDtypeStruct(s, d)
                                  for s, d in dys], jax.random.key(62)))
            inputs = tuple(inputs) + (() if bias else (None,))
            device = sharding or jax.sharding.SingleDeviceSharding(
                jax.devices()[0])
            if sharding is None:
                inputs, dy = jax.device_put(
                    (inputs, dy), row_major((inputs, dy), device))
            item, out_item = 2, jnp.dtype(out_dtype).itemsize
            need = {"fwd": rows * S * (3 if gated else 1) * span * item
                    + rows * S * span * out_item,
                    "fwd+bwd": 2 * rows * S * (3 if gated else 1) * span * item
                    + rows * S * span * out_item}
            need["fwd+bwd"] += need["fwd"]
            mine = rule(S, full, offset, widths, max(item, out_item), gated)
            f = dict(zip(("xla", "kernel"),
                         forms(offset, widths, True, out_dtype, gated)))
            cands = [("xla", None)] + [
                (f"kernel {b[0]}x{b[1]}" + ("*" if b == mine else ""), b)
                for b in dict.fromkeys(
                    [mine] + (GATED_BLOCKS if gated else BLOCKS))
                if b and S % b[0] == 0
                and (args.tiny and b == mine or not args.tiny)
                and (gated and span % b[1] == 0 or not gated and (
                    full == span and b[1] <= max(span, 512)
                    or offset % b[1] == 0
                    and all(w % b[1] == 0 for w in widths)))]
            want = None
            for label, block in cands:
                row = {"shape": name, "candidate": label,
                       **{f"need_{k}_ms": round(v / PEAK_BYTES_S * 1e3, 4)
                          for k, v in need.items()}}
                if block:
                    conv_kernel.blocks = lambda *_, block=block: block
                # a fresh function a candidate: jit keeps traces by function
                runs = passes(lambda *a, g=f[label.split()[0]]: g(*a),
                              (inputs, dy), device)
                try:
                    for which, run in runs.items():
                        sub = {}
                        got = sweep.timed(sub, run, (inputs, dy), args,
                                          sharding)
                        row[which] = sub
                        if which == "fwd+bwd" and got is not None:
                            if want is None:
                                want = got
                            row["off"] = max(sweep.close(got, want))
                except Exception as e:  # the compiler's refusal, mostly
                    row["refused"] = str(e).splitlines()[0][:300]
                finally:
                    conv_kernel.blocks = rule
                sweep.write(out, row)
                report(row)
            if args.no_layers or config_name is None:
                continue
            run, operands = one_layer(kind, config_name, family, rows, S)
            if sharding is None:
                operands = drawn(operands, jax.random.key(63))
            else:
                operands = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=sharding),
                    operands)
            for label in ("xla", "kernel"):
                conv_kernel.on_chip = (lambda: False) if label == "xla" \
                    else on_chip if not args.tiny else (lambda: True)
                row = {"shape": name, "candidate": f"layer {kind} {label}"}
                try:
                    sub = {}
                    sweep.timed(sub, jax.jit(lambda *a: run(*a)), operands,
                                args, sharding)
                    row["fwd+bwd"] = sub
                except Exception as e:
                    row["refused"] = str(e).splitlines()[0][:300]
                finally:
                    conv_kernel.on_chip = on_chip
                sweep.write(out, row)
                report(row)


def report(row: dict) -> None:
    if "refused" in row:
        print(f"{row['shape']:14s} {row['candidate']:20s} refused: "
              f"{row['refused']}", flush=True)
        return

    def cell(which):
        sub = row.get(which, {})
        got = sub.get("ms", sub.get("compile_s", sub.get("first_call_s")))
        need = row.get(f"need_{which}_ms")
        return f"{got!s:>9s}" + (f" ({need:.3f})" if need else "")

    print(f"{row['shape']:14s} {row['candidate']:20s} "
          f"{row.get('off', 0.0):8.1e} | {cell('fwd')}  {cell('fwd+bwd')}",
          flush=True)


if __name__ == "__main__":
    main()
