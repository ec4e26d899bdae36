#!/usr/bin/env python3
"""Sweep the splash kernel's block sizes at the shapes the benchmark's cells
run, the kernel alone: ``chiprun -- python3 scripts/splash_block_sweep.py``.

For every shape it times, in bf16 and as the mean of ``--calls`` calls a
round (the least of ``--rounds`` rounds is reported beside the mean of all),

- the forward alone under each candidate (block_q, block_kv,
  block_kv_compute), the backward's blocks at 512;
- forward + fused backward (``jax.vjp`` of the call pulled back along a fixed
  cotangent: the forward that keeps its residuals, the dkv kernel and the
  ``reduce`` that sums its dq partials, all in what is timed) under each
  candidate (block_q_dkv, block_kv_dkv, block_kv_dkv_compute), the forward's
  blocks at 512;
- both under what ``ops.attention.splash_blocks`` picks for the row.

A candidate the compiler refuses (scoped VMEM) is recorded as refused and the
sweep goes on.  One JSON line a measurement goes to ``--out`` with the
geometry's counts as the first-call record has them, and a table a shape to
stdout.  ``--compile-only`` lowers and compiles every candidate for a
described v5e on a machine without one (no times): what the compiler refuses
there costs no chip time.  ``--tiny`` is the rehearsal on the CPU in interpret
mode."""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import sweep_common as sweep

from ray_tpu.ops import attention
from ray_tpu.ops.attention import SplashBlocks

#: name, cell(s), mask kind, positions, query heads, kv heads, head_dim (one
#: number, or the q.k head's and the v head's), batch
SHAPES = [
    ("blockdiff-16384-32x4-128-b1", "sdar-ep8-s8192", "block_diffusion",
     16384, 32, 4, 128, 1),
    ("causal-8192-32x8-128-b1", "mistral7b-s8192", "causal",
     8192, 32, 8, 128, 1),
    ("causal-8192-32x2-128-b2", "nemotron-ep16-s8192", "causal",
     8192, 32, 2, 128, 2),
    ("causal-4096-32x8-128-b1", "mistral7b-fsdp4-s4096", "causal",
     4096, 32, 8, 128, 1),
    ("causal-4096-16x16-128-b2", "olmoe-s4096", "causal",
     4096, 16, 16, 128, 2),
    ("causal-1024-32x8-128-b8", "mistral7b-s1024", "causal",
     1024, 32, 8, 128, 8),
    ("causal-1024-25x25-64-b16", "gpt2xl-s1024", "causal",
     1024, 25, 25, 64, 16),
    ("causal-8192-32x32-192v128-b1", "joyai-ep16-s8192", "causal",
     8192, 32, 32, (192, 128), 1),
]
TINY = [("blockdiff-512-4x2-32-b1", "-", "block_diffusion", 512, 4, 2, 32, 1),
        ("causal-256-4x2-32-b2", "-", "causal", 256, 4, 2, 32, 2),
        ("causal-256-2x2-48v32-b1", "-", "causal", 256, 2, 2, (48, 32), 1)]
BLOCK_LENGTH = 4


def candidates(seq_len: int, sizes):
    """(block_q, block_kv, block_kv_compute) capped at the row, each once."""
    seen = []
    for q, kv in itertools.product(sizes, sizes):
        for kvc in (sizes[0], kv):
            c = (min(q, seq_len), min(kv, seq_len), min(kvc, kv, seq_len))
            if c not in seen:
                seen.append(c)
    return seen


def measure(shape, blocks, backward: bool, args, topo_sharding=None):
    """One candidate at one shape: a row of the output.  With a sharding of a
    described device the call is compiled and not run."""
    name, cell, kind, S, H, KV, hd, B = shape
    hd, hd_v = hd if isinstance(hd, tuple) else (hd, hd)
    row = {"shape": name, "cell": cell, "pass": "fwd+bwd" if backward
           else "fwd", "blocks": list(blocks)}

    def call(q, k, v, do):
        kernel, counts = attention._splash_kernel(
            S, H, hd, True, BLOCK_LENGTH if kind == "block_diffusion" else 0,
            blocks)
        row.update(counts)
        run = jax.vmap(kernel)
        if not backward:
            return run(q, k, v)
        _, pull = jax.vjp(run, q, k, v)
        return pull(do)

    shapes = [((B, heads, S, width), jnp.bfloat16) for heads, width in (
        (H, hd), (KV, hd), (KV, hd_v), (H, hd_v))]  # q, k, v, do
    operands = tuple(sweep.abstract(shapes, topo_sharding))
    if topo_sharding is None:
        keys = jax.random.split(jax.random.key(0), 4)
        operands = [jax.random.normal(k, a.shape, a.dtype)
                    for k, a in zip(keys, operands)]
    try:
        sweep.timed(row, jax.jit(call), operands, args, topo_sharding)
    except Exception as e:  # the compiler's refusal: recorded, sweep goes on
        text = str(e)
        at = text.find("vmem")
        row["refused"] = (text[max(0, at - 200):at + 200] if at >= 0
                          else text[:400]).replace("\n", " ")
    return row


def main():
    args = sweep.arguments(
        __doc__, "splash_block_sweep", tiny_calls=2,
        **{"--shapes": dict(nargs="*", help="names; default all")})
    shapes, sizes, base = SHAPES, [512, 1024, 2048], 512
    if args.tiny:
        shapes, sizes, base = TINY, [128, 256], 128
    if args.shapes:
        shapes = [s for s in shapes if s[0] in args.shapes]
    sharding = sweep.device(args)

    with open(args.out, "a") as out:
        for shape in shapes:
            S, hd = shape[3], shape[6]
            hd = hd[0] if isinstance(hd, tuple) else hd  # the rule reads q.k's
            square = SplashBlocks.square(base)
            rule = attention.splash_blocks(S, hd)
            plan = [("fwd", square._replace(q=q, kv=kv, kv_compute=kvc), False)
                    for q, kv, kvc in candidates(S, sizes)]
            plan += [("bwd", square._replace(q_bwd=q, kv_bwd=kv,
                                             kv_bwd_compute=kvc), True)
                     for q, kv, kvc in candidates(S, sizes)]
            plan += [("rule", rule, False), ("rule", rule, True)]
            print(f"\n== {shape[0]} ({shape[1]}); rule {tuple(rule)}",
                  flush=True)
            print("swept pass     blocks(q,kv,kvc | q,kv,kvc bwd)"
                  "            ms   work  cut  steps f/b  partials",
                  flush=True)
            for swept, blocks, backward in plan:
                row = measure(shape, blocks.capped(S), backward, args,
                              sharding)
                row["swept"] = swept
                sweep.write(out, row)
                b = row["blocks"]
                what = (f"{row['ms']:9.3f}" if "ms" in row else
                        "  refused" if "refused" in row else
                        f"{row['compile_s']:8.1f}s")
                print(f"{swept:5s} {row['pass']:8s} {str(b[:3]):20s}"
                      f"{str(b[3:]):20s} {what} "
                      f"{row.get('attn_blocks', ''):>5} "
                      f"{row.get('attn_blocks_cut', ''):>4} "
                      f"{row.get('attn_grid_steps_fwd', ''):>5}/"
                      f"{row.get('attn_grid_steps_bwd', ''):<5} "
                      f"{row.get('attn_dq_partials', ''):>4}"
                      + (f"  {row['refused'][:160]}" if "refused" in row
                         else ""), flush=True)


if __name__ == "__main__":
    main()
