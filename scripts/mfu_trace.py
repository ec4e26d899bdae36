"""Per-component attribution of the GPT-2-small train step (PERF.md r3).

Whole-step ablations plus an optional jax.profiler trace.

Usage: python scripts/mfu_trace.py [--trace DIR]
"""

import argparse
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp


def bench(fn, *args, iters=15):
    out = fn(*args)
    _sync(out)
    for _ in range(3):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters * 1000


def _sync(out):
    jax.block_until_ready(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None)
    args = ap.parse_args()

    from ray_tpu.models import gpt2

    cfg = gpt2.GPTConfig(remat_policy="attn_outside")
    B, S = 16, cfg.seq_len
    key = jax.random.PRNGKey(0)
    params = gpt2.init_params(cfg, key)
    params = jax.device_put(params)
    tok = jax.random.randint(key, (B, S), 0, 50257)
    tgt = jax.random.randint(key, (B, S), 0, 50257)
    opt = gpt2.make_optimizer()
    opt_state = opt.init(params)
    step = jax.jit(gpt2.make_train_step(cfg, opt))

    # 1. full step
    t_full = bench(step, params, opt_state, tok, tgt)
    print(f"full train step:            {t_full:7.2f} ms")

    # 2. loss fwd+bwd only (no optimizer)
    vg = jax.jit(lambda p: jax.value_and_grad(gpt2.loss_fn)(p, tok, tgt, cfg))
    t_vg = bench(vg, params)
    print(f"loss fwd+bwd (no optim):    {t_vg:7.2f} ms   (optimizer ~{t_full - t_vg:.2f})")

    # 3. forward only
    fwd = jax.jit(lambda p: gpt2.loss_fn(p, tok, tgt, cfg))
    t_fwd = bench(fwd, params)
    print(f"loss forward only:          {t_fwd:7.2f} ms   (backward ~{t_vg - t_fwd:.2f})")

    # 4. trunk only fwd+bwd (head replaced by cheap sum)
    def trunk_loss(p):
        x = gpt2.forward_hidden(p, tok, cfg)
        return jnp.mean(x.astype(jnp.float32) ** 2)

    t_trunk = bench(jax.jit(jax.value_and_grad(trunk_loss)), params)
    print(f"trunk-only fwd+bwd:         {t_trunk:7.2f} ms   (head ~{t_vg - t_trunk:.2f})")

    # 5. trunk with attention replaced by identity (measures attention share)
    import ray_tpu.models.gpt2 as g

    orig_attn = g._attention
    try:
        g._attention = lambda q, k, v, config: v
        t_noattn = bench(jax.jit(jax.value_and_grad(trunk_loss)), params)
    finally:
        g._attention = orig_attn
    print(f"trunk, attention=identity:  {t_noattn:7.2f} ms   (attention ~{t_trunk - t_noattn:.2f})")

    # 6. trunk with layernorm in bf16 (measures fp32 LN traffic)
    orig_ln = g._layernorm

    def ln_bf16(x, scale, bias, eps=1e-5):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(x.dtype) \
            + bias.astype(x.dtype)

    try:
        g._layernorm = ln_bf16
        t_lnbf16 = bench(jax.jit(jax.value_and_grad(trunk_loss)), params)
    finally:
        g._layernorm = orig_ln
    print(f"trunk, bf16 layernorm:      {t_lnbf16:7.2f} ms   (fp32-LN cost ~{t_trunk - t_lnbf16:.2f})")

    mfu = gpt2.flops_per_token(cfg) * B * S / (t_full / 1000) / 197e12 * 100
    print(f"implied MFU at {t_full:.1f} ms:  {mfu:.2f}%")

    if args.trace:
        with jax.profiler.trace(args.trace):
            for _ in range(3):
                out = step(params, opt_state, tok, tgt)
            _sync(out)
        print(f"trace written to {args.trace}")


if __name__ == "__main__":
    main()
