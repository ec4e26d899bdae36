"""Long-context ring-attention artifact (VERDICT r4 #2).

Runs the ring-attention body fused (splash flash kernel per rotation block)
vs un-fused (streaming-LSE einsum blocks) on the real chip at S=8192 and
reports fwd+bwd step time and peak HBM.  On one chip the ring degenerates to
world=1 — a single diagonal block — which isolates exactly what the fusion
changes: whether the (B, H, S_local, S_local) score tensor hits HBM.

Usage: python scripts/bench_ring.py   (writes BENCH_RING.json)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.parallel import MeshSpec, make_mesh


def _bench(impl: str, mesh, q, k, v, iters: int = 20):
    dev = jax.local_devices()[0]

    def loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh=mesh, causal=True,
                                      impl=impl) ** 2)

    # Returns a scalar so the timed program also reads every gradient.
    def step(q, k, v):
        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return sum(jnp.sum(x.astype(jnp.float32) ** 2) for x in g)

    step = jax.jit(step)
    jax.block_until_ready(step(q, k, v))  # compile + warm
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0)
    t0 = time.perf_counter()
    s = None
    for _ in range(iters):
        s = step(q, k, v)
    jax.block_until_ready(s)
    dt = (time.perf_counter() - t0) / iters
    return dt * 1e3, peak / (1 << 20)


def main():
    B, S, H, D = 1, 8192, 8, 128
    mesh = make_mesh(MeshSpec(seq=1))
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16) for kk in ks)
    sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(("data", "fsdp"), "seq"))
    q, k, v = (jax.device_put(a, sh) for a in (q, k, v))

    out = {"shape": f"B{B} S{S} H{H} D{D} bf16", "device": str(jax.devices()[0])}
    for impl in ("einsum", "fused"):
        ms, peak_mib = _bench(impl, mesh, q, k, v)
        out[f"{impl}_fwd_bwd_ms"] = round(ms, 2)
        if peak_mib:
            out[f"{impl}_peak_mib"] = round(peak_mib, 1)
    out["speedup"] = round(out["einsum_fwd_bwd_ms"] / out["fused_fwd_bwd_ms"], 2)

    # Memory artifact: at S=16384 the un-fused body's fp32 score block is
    # 8 GiB x fwd+bwd copies — it must OOM on a 16 GiB chip while the fused
    # kernel scales quadratic-free.  (peak_bytes_in_use leaves out a
    # program's temporaries on this runtime — PERF.md, Bring-up — so the OOM
    # is the evidence, not the statistic.)
    S2 = 16384
    ks = jax.random.split(jax.random.key(1), 3)
    q2, k2, v2 = (jax.random.normal(kk, (B, S2, H, D), jnp.bfloat16)
                  for kk in ks)
    q2, k2, v2 = (jax.device_put(a, sh) for a in (q2, k2, v2))
    for impl in ("einsum", "fused"):
        try:
            ms, _ = _bench(impl, mesh, q2, k2, v2, iters=5)
            out[f"{impl}_s16k_fwd_bwd_ms"] = round(ms, 2)
        except Exception as e:  # noqa: BLE001 — XLA raises RESOURCE_EXHAUSTED
            out[f"{impl}_s16k_fwd_bwd_ms"] = f"OOM ({type(e).__name__})"
    print(json.dumps(out))
    with open("BENCH_RING.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
