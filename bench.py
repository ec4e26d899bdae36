"""Headline benchmark: GPT-2 (124M) training throughput + MFU on real TPU.

Prints ONE JSON line:
  {"metric": "gpt2_train_mfu", "value": <MFU %>, "unit": "%", "vs_baseline": ...}

vs_baseline is MFU / 45% — the north-star target from BASELINE.md (the
reference publishes no TPU/MFU numbers; 45% MFU on v5e is the bar the new
framework must set).  Extra detail goes to stderr only.

Runs on the TPU or not at all: a CPU step time divided by a TPU peak is not a
utilization.  Single process, all local chips, pure data parallel.
"""

from __future__ import annotations

import json
import statistics
import sys
import time

import numpy as np


def main() -> None:
    import jax

    from ray_tpu._private.accelerators import device_peaks
    from ray_tpu.models import gpt2
    from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh
    from ray_tpu.parallel.compile_cache import configure_compile_cache
    from ray_tpu.parallel.train_state import create_sharded_state, jit_train_step

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU; jax found backend "
            f"{jax.default_backend()!r} with devices {jax.devices()}")
    devices = jax.devices()
    n_dev = len(devices)
    peak = device_peaks(devices[0].device_kind).flops * n_dev
    cache_dir = configure_compile_cache()
    print(f"devices: {devices}  compile cache: {cache_dir}", file=sys.stderr)

    # 124M, seq 1024, bf16, splash attention.  PERF.md r3:
    # - remat_policy="attn_outside" keeps the splash kernel's own
    #   residuals across the backward (save_attn re-ran the splash
    #   FORWARD inside the bwd, ~11 ms/step);
    # - scan_layers=False unrolls the 12-layer loop, dropping the scan's
    #   dynamic-update-slice residual stacking (~10 ms/step) for a longer
    #   first compile.
    config = gpt2.GPTConfig(remat_policy="attn_outside", scan_layers=False)
    batch_per_chip = 16
    B = batch_per_chip * n_dev

    spec = MeshSpec(data=n_dev)
    mesh = make_mesh(spec, devices)
    optimizer = gpt2.make_optimizer(learning_rate=3e-4)
    params, opt_state = create_sharded_state(
        lambda key: gpt2.init_params(config, key),
        gpt2.logical_axes(config),
        mesh,
        jax.random.key(0),
        optimizer,
    )
    # mesh=: the splash kernel divides itself over the ambient mesh.
    step = jit_train_step(gpt2.make_train_step(config, optimizer), mesh=mesh)

    batch_sh = batch_sharding(mesh)
    toks = np.random.default_rng(0).integers(
        0, config.vocab_size, (B, config.seq_len + 1)).astype(np.int32)
    # Host array straight to its shards; never the whole batch on device 0.
    tokens = jax.device_put(toks[:, :-1], batch_sh)
    targets = jax.device_put(toks[:, 1:], batch_sh)

    # Warmup (compile + 2 steps), reported apart as set-up time.
    t0 = time.perf_counter()
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    warm_loss = float(jax.block_until_ready(loss))
    print(f"warmup (incl. compile): {time.perf_counter() - t0:.1f}s "
          f"loss={warm_loss:.3f}", file=sys.stderr)

    # Three windows, each ending in block_until_ready; the median is the
    # figure and the spread between windows is printed beside it.
    n_steps = 10
    windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n_steps):
            params, opt_state, loss = step(params, opt_state, tokens, targets)
        jax.block_until_ready(loss)
        windows.append(time.perf_counter() - t0)
    final_loss = float(loss)
    dt = statistics.median(windows)

    tokens_total = n_steps * B * config.seq_len
    tokens_per_sec = tokens_total / dt
    flops = gpt2.flops_per_token(config) * tokens_per_sec
    mfu = flops / peak
    tokens_per_sec_chip = tokens_per_sec / n_dev

    print(
        f"steps={n_steps} batch={B} seq={config.seq_len} time={dt:.2f}s "
        f"(windows {min(windows):.2f}-{max(windows):.2f}s) "
        f"tokens/s={tokens_per_sec:,.0f} tokens/s/chip={tokens_per_sec_chip:,.0f} "
        f"model_flops/s={flops/1e12:.1f}T peak={peak/1e12:.0f}T MFU={mfu*100:.1f}% "
        f"loss={final_loss:.3f}",
        file=sys.stderr,
    )

    print(json.dumps({
        "metric": "gpt2_124m_train_mfu",
        "value": round(mfu * 100, 2),
        "unit": "%",
        "vs_baseline": round(mfu / 0.45, 3),
        "tokens_per_sec_per_chip": round(tokens_per_sec_chip, 1),
        "n_chips": n_dev,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": n_dev},
    }))


if __name__ == "__main__":
    main()
