"""Fused LM-head CE artifact (VERDICT r4 #8): demonstrate the kernel
winning in its winning regime, and losing where the cost model says it
should lose.

Head-only configs (fwd+bwd wrt x and W, real chip), each measured against
dense with bf16-materialized logits AND dense with fp32 logits (exact
softmax — the parity config; the fused kernel is fp32-exact by
construction):

  gpt2_small_head  D=768, V=50304 — DENSE wins both ways (honest row)
  small_head_fp32  D=128, V=65536 — dense-fp32 is HBM-traffic-bound;
                   FUSED wins (the cost model's predicted regime)
  oom_regime       D=512, V=131072, 64k tokens — dense logits cannot
                   materialize; fused runs.  An absolute win.

Writes BENCH_FUSED_CE.json.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from ray_tpu._private.accelerators import device_peaks
from ray_tpu.ops.fused_ce import fused_ce_wins, fused_lm_head_ce


def dense_ce(x, wte, targets, logits_dtype=jnp.bfloat16):
    logits = jnp.einsum("bsd,vd->bsv", x, wte,
                        preferred_element_type=logits_dtype)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0].astype(jnp.float32)
    return jnp.mean(lse - tgt)


def dense_ce_fp32(x, wte, targets):
    return dense_ce(x, wte, targets, jnp.float32)


def bench(fn, x, wte, targets, iters=10):
    def step(x, wte):
        l, (dx, dw) = jax.value_and_grad(
            lambda x, w: fn(x, w, targets), argnums=(0, 1))(x, wte)
        return l + jnp.sum(dx.astype(jnp.float32) ** 2) * 0 \
            + jnp.sum(dw.astype(jnp.float32) ** 2) * 0

    step = jax.jit(step)
    jax.block_until_ready(step(x, wte))  # compile + warm
    t0 = time.perf_counter()
    s = None
    for _ in range(iters):
        s = step(x, wte)
    jax.block_until_ready(s)
    return (time.perf_counter() - t0) / iters * 1e3


def run_config(name, B, S, D, V, out):
    kx, kw, kt = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(kx, (B, S, D), jnp.bfloat16)
    wte = jax.random.normal(kw, (V, D), jnp.bfloat16) * 0.02
    targets = jax.random.randint(kt, (B, S), 0, V)
    peaks = device_peaks(jax.devices()[0].device_kind)
    row = {"tokens": B * S, "d_model": D, "vocab": V,
           "cost_model_predicts_fused_bf16": fused_ce_wins(D, 2, peaks),
           "cost_model_predicts_fused_fp32": fused_ce_wins(D, 4, peaks)}
    for impl, fn in (("dense_bf16", dense_ce), ("dense_fp32", dense_ce_fp32),
                     ("fused", fused_lm_head_ce)):
        try:
            row[f"{impl}_ms"] = round(bench(fn, x, wte, targets), 2)
        except Exception as e:  # noqa: BLE001 — RESOURCE_EXHAUSTED expected
            row[f"{impl}_ms"] = f"OOM ({type(e).__name__})"
    if isinstance(row.get("fused_ms"), float):
        for base in ("dense_bf16", "dense_fp32"):
            if isinstance(row.get(f"{base}_ms"), float):
                row[f"fused_vs_{base}"] = round(
                    row[f"{base}_ms"] / row["fused_ms"], 2)
    out[name] = row
    print(name, row, file=sys.stderr)


def main():
    out = {"device": str(jax.devices()[0])}
    run_config("gpt2_small_head", 16, 1024, 768, 50304, out)
    run_config("small_head_fp32", 16, 1024, 128, 65536, out)
    run_config("oom_regime", 8, 8192, 512, 131072, out)
    print(json.dumps(out))
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_FUSED_CE.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
