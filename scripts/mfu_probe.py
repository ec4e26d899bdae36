"""MFU probe: one operator-facing entry point for step-time questions.

Consolidates the PERF.md probe-script family (mfu_probe2..9, mfu_sweep*)
behind flags, and routes the headline mode through the train profiler
(ray_tpu/train/profiler.py) instead of ad-hoc timing loops — the same
attribution machinery a real Trainer run exports continuously.

Modes:
  step        (default) run N train steps with an active StepProfiler:
              prints per-step wall, the data_wait/h2d/collective/
              ckpt_block/compute buckets, tokens/s and MFU.
  components  attention impl x block, LM head variants, trunk fwd, and
              remat-policy full steps, each vs its roofline (the old
              mfu_probe.py).
  sweep       remat x batch x loss_chunk grid, one line per config, best
              MFU summarized (the old mfu_sweep.py; --quick for the short
              grid).

Examples:
  python scripts/mfu_probe.py                        # profiler-driven step
  python scripts/mfu_probe.py --config small --batch-per-chip 32 --steps 20
  python scripts/mfu_probe.py --mode components
  python scripts/mfu_probe.py --mode sweep --quick
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

PEAK = 197e12     # v5e bf16 dense, per chip
HBM_BW = 819e9    # v5e HBM bytes/s


def _build_config(args):
    from ray_tpu.models import gpt2

    config = (gpt2.GPTConfig.tiny() if args.config == "tiny"
              else gpt2.GPTConfig.small())
    import dataclasses

    kw = {}
    if args.remat_policy:
        kw["remat_policy"] = args.remat_policy
    if args.no_remat:
        kw["remat"] = False
    if args.loss_chunk:
        kw["loss_chunk"] = args.loss_chunk
    if args.attn_impl:
        kw["attn_impl"] = args.attn_impl
    if args.seq_len:
        kw["seq_len"] = args.seq_len
    return dataclasses.replace(config, **kw) if kw else config


# --------------------------------------------------------------------- step
def run_step_mode(args) -> None:
    """Profiler-driven: the numbers here are the ones a Trainer run
    exports live as ray_tpu_train_* gauges — same code path.  The step
    is a ``jit_train_step`` ``TrainStep``, so the run also exercises the
    device-telemetry plane: exactly one first-compile should land in
    ``device_telemetry.compile_records("train_step")``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.train_state import jit_train_step
    from ray_tpu.train import profiler as train_profiler
    from ray_tpu.util import device_telemetry

    config = _build_config(args)
    devices = jax.devices()
    n_dev = len(devices)
    B = args.batch_per_chip * n_dev
    S = config.seq_len
    peak = (args.peak_flops or PEAK) * n_dev

    opt = gpt2.make_optimizer(learning_rate=3e-4)
    params = gpt2.init_params(config, jax.random.key(0))
    opt_state = opt.init(params)
    step = jit_train_step(gpt2.make_train_step(config, opt))

    rng = np.random.default_rng(0)
    toks = rng.integers(0, config.vocab_size, (B, S + 1), dtype=np.int64)
    t = jnp.asarray(toks, jnp.int32)
    tokens, targets = t[:, :-1], t[:, 1:]

    prof = train_profiler.StepProfiler(
        run_name="mfu_probe", rank=0,
        flops_per_step=gpt2.flops_per_token(config) * B * S,
        tokens_per_step=B * S, peak_flops=peak)
    train_profiler.activate(prof)
    try:
        for _ in range(3):  # compile + warm outside the profiled window
            params, opt_state, loss = step(params, opt_state, tokens, targets)
        float(loss)
        prof.step_boundary()  # discard the warmup window
        for _ in range(args.steps):
            params, opt_state, loss = step(params, opt_state, tokens, targets)
            float(loss)  # device sync = the step's true end
            prof.step_boundary()
    finally:
        train_profiler.activate(None)

    rows = [r for r in prof.history if r["step"] > 0]
    if not rows:
        print("no profiled steps", flush=True)
        return
    walls = sorted(r["wall"] for r in rows)
    wall = walls[len(walls) // 2]
    print(f"{args.config} GPT-2  B={B} S={S}  {n_dev} device(s)  "
          f"{args.steps} steps", flush=True)
    print(f"  median step {wall*1e3:8.2f} ms   "
          f"tokens/s {B*S/wall:10,.0f}   "
          f"MFU {prof.flops_per_step/wall/peak*100:5.1f}%", flush=True)
    last = rows[-1]
    print("  attribution (last step):", flush=True)
    for bucket in ("data_wait", "h2d", "collective", "ckpt_block", "compute"):
        frac = last[bucket] / last["wall"] if last["wall"] else 0.0
        print(f"    {bucket:10s} {last[bucket]*1e3:8.2f} ms  "
              f"{frac*100:5.1f}%", flush=True)
    total = sum(last[b] for b in ("data_wait", "h2d", "collective",
                                  "ckpt_block", "compute"))
    print(f"    {'sum':10s} {total*1e3:8.2f} ms  "
          f"(wall {last['wall']*1e3:.2f} ms)", flush=True)
    compiles = device_telemetry.compile_records("train_step")
    print(f"  xla compiles: {len(compiles)} "
          f"({', '.join(c['trigger'] for c in compiles) or 'none'})",
          flush=True)
    print(f"  final loss {float(loss):.3f}", flush=True)


# --------------------------------------------------------------- components
def timeit(fn, *args, n=20, warmup=3):
    """fn is wrapped to reduce its output to ONE scalar on device, so the
    timed program computes every output and the sync reads four bytes."""
    import jax
    import jax.numpy as jnp

    scalar_fn = jax.jit(lambda *a: jax.tree.reduce(
        lambda acc, x: acc + jnp.sum(x).astype(jnp.float32), fn(*a),
        jnp.zeros((), jnp.float32)))
    for _ in range(warmup):
        out = scalar_fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = scalar_fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def run_components_mode(args) -> None:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2

    B, S, H, hd, D, V = 16, 1024, 12, 64, 768, 50304
    L = 12
    key = jax.random.key(0)

    # ---------------- attention: impl x block ----------------
    q = jax.random.normal(key, (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, H, hd), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, H, hd), jnp.bfloat16)
    attn_flops = 4 * B * S * S * H * hd * 0.5  # causal halves the work
    print(f"attention (B{B} S{S} H{H} hd{hd}), causal roofline "
          f"{attn_flops/PEAK*1e3:.2f}ms fwd:", flush=True)

    from ray_tpu.ops.attention import splash_attention

    for tag, fn in [
        ("xla", lambda q, k, v: gpt2._attention(q, k, v, gpt2.GPTConfig(attn_impl="xla"))),
        ("splash b256", partial(splash_attention, block_q=256, block_kv=256)),
        ("splash b512", partial(splash_attention, block_q=512, block_kv=512)),
        ("splash b1024", partial(splash_attention, block_q=1024, block_kv=1024)),
    ]:
        try:
            dt = timeit(fn, q, k, v)
            grad_fn = jax.grad(lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2))
            dtg = timeit(grad_fn, q, k, v)
            print(f"  {tag:12s} fwd {dt*1e3:7.2f}ms  fwd+bwd {dtg*1e3:7.2f}ms", flush=True)
        except Exception as e:
            print(f"  {tag:12s} FAILED {type(e).__name__}: {str(e)[:100]}", flush=True)

    # ---------------- LM head ----------------
    x = jax.random.normal(key, (B, S, D), jnp.bfloat16)
    wte = jax.random.normal(key, (V, D), jnp.bfloat16)
    tgt = jnp.zeros((B, S), jnp.int32)
    head_flops = 2 * B * S * D * V
    head_bytes = B * S * V * 4
    print(f"\nLM head roofline: matmul {head_flops/PEAK*1e3:.2f}ms, "
          f"fp32 logits write {head_bytes/HBM_BW*1e3:.2f}ms", flush=True)

    def head_loss(x, wte, tgt):
        logits = jnp.einsum("bsd,vd->bsv", x, wte, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        t = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - t)

    print(f"  head loss fwd      {timeit(head_loss, x, wte, tgt)*1e3:7.2f}ms", flush=True)
    print(f"  head loss fwd+bwd  {timeit(jax.grad(head_loss, argnums=(0, 1)), x, wte, tgt)*1e3:7.2f}ms", flush=True)

    def head_loss_chunk(x, wte, tgt, C=256):
        n = S // C
        xs = x.reshape(B, n, C, D).swapaxes(0, 1)
        ts = tgt.reshape(B, n, C).swapaxes(0, 1)

        @jax.checkpoint
        def cl(x_c, t_c):
            logits = jnp.einsum("bsd,vd->bsv", x_c, wte, preferred_element_type=jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            t = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
            return jnp.sum(lse - t)

        import jax.lax as lax
        total, _ = lax.scan(lambda a, xt: (a + cl(*xt), None), jnp.zeros((), jnp.float32), (xs, ts))
        return total / (B * S)

    print(f"  chunk256 fwd       {timeit(head_loss_chunk, x, wte, tgt)*1e3:7.2f}ms", flush=True)
    print(f"  chunk256 fwd+bwd   {timeit(jax.grad(head_loss_chunk, argnums=(0, 1)), x, wte, tgt)*1e3:7.2f}ms", flush=True)

    # ---------------- trunk fwd / full step breakdown ----------------
    config = gpt2.GPTConfig()
    params = gpt2.init_params(config, key)
    toks = jnp.zeros((B, S), jnp.int32)
    tgts = jnp.zeros((B, S), jnp.int32)

    trunk_flops = 2 * (gpt2.num_params(config) - V * D) * B * S + attn_flops * L
    print(f"\ntrunk fwd roofline {trunk_flops/PEAK*1e3:.2f}ms", flush=True)
    print(f"  trunk fwd          {timeit(lambda p, t: gpt2.forward_hidden(p, t, config), params, toks)*1e3:7.2f}ms", flush=True)
    print(f"  loss fwd           {timeit(lambda p, t, g: gpt2.loss_fn(p, t, g, config), params, toks, tgts)*1e3:7.2f}ms", flush=True)
    print(f"  loss fwd+bwd       {timeit(jax.grad(lambda p, t, g: gpt2.loss_fn(p, t, g, config)), params, toks, tgts)*1e3:7.2f}ms", flush=True)

    # ---------------- remat policies, full step ----------------
    print("\nfull train step by remat policy:", flush=True)
    import dataclasses

    for tag, kw in [
        ("save_attn (r1)", dict()),
        ("save_attn chunk256", dict(loss_chunk=256)),
        ("dots_saveable", dict(remat_policy="dots")),
        ("everything_saveable", dict(remat_policy="everything")),
    ]:
        try:
            c = dataclasses.replace(config, **kw)
            opt = gpt2.make_optimizer()
            p2 = gpt2.init_params(c, key)
            o2 = opt.init(p2)
            step = jax.jit(gpt2.make_train_step(c, opt), donate_argnums=(0, 1))
            for _ in range(3):
                p2, o2, loss = step(p2, o2, toks, tgts)
            float(loss)
            t0 = time.perf_counter()
            n = 10
            for _ in range(n):
                p2, o2, loss = step(p2, o2, toks, tgts)
            float(loss)
            dt = (time.perf_counter() - t0) / n
            mfu = gpt2.flops_per_token(c) * B * S / dt / PEAK
            print(f"  {tag:22s} {dt*1e3:7.1f}ms  MFU {mfu*100:5.1f}%", flush=True)
        except Exception as e:
            print(f"  {tag:22s} FAILED {type(e).__name__}: {str(e)[:90]}", flush=True)


# -------------------------------------------------------------------- sweep
def run_sweep_config(tag, config, batch_per_chip, n_steps=8):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2
    from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh
    from ray_tpu.parallel.train_state import create_sharded_state, jit_train_step

    devices = jax.devices()
    n_dev = len(devices)
    B = batch_per_chip * n_dev
    mesh = make_mesh(MeshSpec(data=n_dev), devices)
    optimizer = gpt2.make_optimizer(learning_rate=3e-4)
    try:
        params, opt_state = create_sharded_state(
            lambda key: gpt2.init_params(config, key),
            gpt2.logical_axes(config), mesh, jax.random.key(0), optimizer)
        step = jit_train_step(gpt2.make_train_step(config, optimizer),
                              mesh=mesh)

        batch_sh = batch_sharding(mesh)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, config.vocab_size, (B, config.seq_len + 1), dtype=np.int64)
        t = jnp.asarray(toks, jnp.int32)
        tokens = jax.device_put(t[:, :-1], batch_sh)
        targets = jax.device_put(t[:, 1:], batch_sh)

        t0 = time.perf_counter()
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, tokens, targets)
        float(loss)
        compile_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        for _ in range(n_steps):
            params, opt_state, loss = step(params, opt_state, tokens, targets)
        final_loss = float(loss)
        dt = time.perf_counter() - t0
    except Exception as e:
        print(f"{tag:55s}  FAILED: {type(e).__name__}: {str(e)[:120]}", flush=True)
        return None

    tokens_per_sec = n_steps * B * config.seq_len / dt
    flops = gpt2.flops_per_token(config) * tokens_per_sec
    peak = PEAK * n_dev
    mfu = flops / peak
    ms = dt / n_steps * 1e3
    print(f"{tag:55s}  {ms:8.1f} ms  {tokens_per_sec:9,.0f} tok/s  "
          f"MFU {mfu*100:5.1f}%  (compile+warm {compile_s:.0f}s, loss {final_loss:.3f})",
          flush=True)
    return mfu


def run_sweep_mode(args) -> None:
    from ray_tpu.models import gpt2

    def cfg(**kw):
        return gpt2.GPTConfig(**kw)

    grid = [
        # (tag, config, batch_per_chip)
        ("baseline r1: save_attn b16", cfg(), 16),
        ("no-remat b16", cfg(remat=False), 16),
        ("no-remat b16 chunk128", cfg(remat=False, loss_chunk=128), 16),
        ("no-remat b16 chunk256", cfg(remat=False, loss_chunk=256), 16),
        ("save_attn b16 chunk256", cfg(loss_chunk=256), 16),
        ("no-remat b32", cfg(remat=False), 32),
        ("no-remat b32 chunk256", cfg(remat=False, loss_chunk=256), 32),
        ("no-remat b32 chunk128", cfg(remat=False, loss_chunk=128), 32),
        ("save_attn b32 chunk256", cfg(loss_chunk=256), 32),
        ("no-remat b64 chunk256", cfg(remat=False, loss_chunk=256), 64),
        ("save_attn b64 chunk256", cfg(loss_chunk=256), 64),
    ]
    if args.quick:
        grid = grid[:4]
    results = {}
    for tag, c, b in grid:
        results[tag] = run_sweep_config(tag, c, b)

    scored = [(m, t) for t, m in results.items() if m is not None]
    if scored:
        best = max(scored)
        print(f"\nBEST: {best[1]}  MFU {best[0]*100:.1f}%", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", choices=("step", "components", "sweep"),
                    default="step")
    ap.add_argument("--config", choices=("small", "tiny"), default="small",
                    help="GPT-2 size preset (step mode)")
    ap.add_argument("--batch-per-chip", type=int, default=16)
    ap.add_argument("--steps", type=int, default=10,
                    help="profiled steps (step mode)")
    ap.add_argument("--seq-len", type=int, default=0,
                    help="override the preset's sequence length")
    ap.add_argument("--peak-flops", type=float, default=0.0,
                    help=f"per-chip peak FLOP/s for MFU (default {PEAK:.0e})")
    ap.add_argument("--remat-policy", default="",
                    help="override remat policy (step mode)")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--loss-chunk", type=int, default=0)
    ap.add_argument("--attn-impl", default="",
                    help="xla | pallas | splash | ring | ulysses")
    ap.add_argument("--quick", action="store_true",
                    help="short grid (sweep mode)")
    args = ap.parse_args(argv)
    if args.mode == "step":
        run_step_mode(args)
    elif args.mode == "components":
        run_components_mode(args)
    else:
        run_sweep_mode(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
