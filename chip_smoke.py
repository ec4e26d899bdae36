"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run as ``python chip_smoke.py`` from the root of a plain copy of the tree, on a
machine with at least one TPU chip.  One process (it holds every local chip;
the only other process it starts is one CPU-only pool worker, stopped by
``ray_tpu.shutdown()``), no network, no file that ``.gitignore`` excludes, data
generated from a seed.  It drives the main training path once through the
entry points a user calls and stops at the first failure:

1. device   — jax must report a TPU backend.
2. runtime  — ``ray_tpu.init()`` with no override registers the chips; a
   process-tier task that imports jax stays off them.
3. train    — GPT-2 124M (full width and depth, S=1024, bf16, 16 sequences per
   chip, ``remat_policy="attn_outside"``, ``scan_layers=False``) for 3 warm-up
   and 10 timed steps through ``JaxTrainer.fit()`` on a ``data=n`` mesh over
   all local chips, fed by the streaming ingest.  The loss must fall as the
   old records say it does on a repeated batch, nothing may compile inside the
   timed window, every chip must hold its shard, and the compiled step must
   contain the Mosaic attention calls at per-chip shapes.
4. kernels  — every Pallas kernel under ``ray_tpu/ops/`` compiled for the chip
   (``interpret=False`` read from the jaxpr, Mosaic custom calls counted in
   the compiled module) at the shape the models use, against its XLA
   reference.

The times it prints are smoke output for orientation, not benchmark metrics.
What it counts of jax's compiles it counts with the benchmark's own
``benchmarks/lib/compile_watch.CompileWatch``.
The last line of stdout is ``{"ok": true, "device": {...}}``; a longer report
goes to ``chiprun_out/chip_smoke.json``.
"""

from __future__ import annotations

import json
import math
import os
import re
import sys
import time

import numpy as np

from benchmarks.lib.compile_watch import CompileWatch

SEQS_PER_CHIP = 16
WARMUP_STEPS = 3
TIMED_STEPS = 10
#: Least fall of the loss from step 1 to step 13 that counts as training.
#: On one repeated batch of 16 sequences the old records show 10.43 after 3
#: steps and 9.56 after 13 (train_state.make_optimizer's docstring), and this
#: script reproduces them (10.98 -> 10.43 -> 9.56); a repeated batch of 64 on
#: four chips falls 0.85 in all (PERF.md, Bring-up).  Half of the smaller fall.
MIN_LOSS_FALL = 0.4
#: bf16 carries 8 mantissa bits (eps 3.9e-3).  A kernel and its XLA reference
#: round in different places, so outputs may differ by a few eps of the
#: largest value and gradients, which chain several bf16 matmuls, by about
#: ten.  Errors are max|a-b| / max|b|.
FWD_TOL = 2e-2
GRAD_TOL = 4e-2
REPORT_DIR = "chiprun_out"


def log(msg: str) -> None:
    print(msg, flush=True)


def cache_verdict(delta: dict) -> str:
    if delta["misses"]:
        return "miss"
    return "hit" if delta["hits"] else "not consulted"


# ---------------------------------------------------------------- 1. device
def device_phase():
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    if backend != "tpu" or not devices:
        raise SystemExit(
            f"chip_smoke needs a TPU and jax found none: default_backend() is "
            f"{backend!r}, devices are {devices}")
    import jaxlib

    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not importable"
    d = devices[0]
    log(f"[device] platform={d.platform} device_kind={d.device_kind!r} "
        f"count={len(devices)} local={len(jax.local_devices())} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu_version}")
    return devices


# --------------------------------------------------------------- 2. runtime
def _child_backend():
    """Runs in a process-tier worker while the driver holds the chips."""
    import jax

    return os.environ.get("JAX_PLATFORMS"), jax.default_backend()


def runtime_phase() -> dict:
    import jax

    import ray_tpu
    from ray_tpu._private.runtime import get_runtime

    ray_tpu.init()
    resources = ray_tpu.cluster_resources()
    n_local = len(jax.local_devices())
    if resources.get("TPU") != float(n_local):
        raise RuntimeError(
            f"ray_tpu.init() registered TPU={resources.get('TPU')} for "
            f"{n_local} local chips (resources: {resources})")
    kind = jax.local_devices()[0].device_kind
    labels = ray_tpu.nodes()[0]["Labels"]
    want = kind.replace(" ", "-").lower()
    if labels.get("accelerator-type") != want:
        raise RuntimeError(f"accelerator-type label {labels!r}, want {want!r}")
    store = "native" if get_runtime().store.plasma is not None \
        else "python fallback (native build failed)"
    log(f"[runtime] resources={resources} labels={labels} "
        f"object_store={store}")

    env, backend = ray_tpu.get(
        ray_tpu.remote(isolation="process")(_child_backend).remote(),
        timeout=300)
    if backend != "cpu":
        raise RuntimeError(
            f"a process-tier worker came up on backend {backend!r} "
            f"(JAX_PLATFORMS={env!r}) while the driver holds the chips")
    log(f"[runtime] process-tier worker: JAX_PLATFORMS={env!r} "
        f"backend={backend!r}")
    return {"resources": resources, "labels": labels, "object_store": store}


# ----------------------------------------------------------------- 3. train
def token_dataset(vocab_size: int, seq_len: int, global_batch: int,
                  n_batches: int, seed: int):
    """A lazy dataset of ``n_batches`` blocks, each the same seeded global
    batch, so the ingest's block shuffle cannot change what a step sees."""
    from ray_tpu import data

    fixed = np.random.default_rng(seed).integers(
        0, vocab_size, (global_batch, seq_len + 1)).astype(np.int32)

    def to_tokens(block):
        rows = block["id"] % global_batch
        return {"tokens": fixed[rows, :-1], "targets": fixed[rows, 1:]}

    return data.range(global_batch * n_batches,
                      parallelism=n_batches).map_batches(to_tokens)


def custom_call_report(hlo: str) -> dict:
    """What the compiled (per-device) module says about its Mosaic calls:
    how many there are, the largest leading dimension among their 4-D
    operands and results (the attention batch each chip computes), and the
    module's collectives."""
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln
             and "custom-call(" in ln]
    lead = 0
    for ln in calls:
        for dims in re.findall(r"\w+\[(\d+(?:,\d+){3})\]", ln):
            lead = max(lead, int(dims.split(",")[0]))
    return {"mosaic_calls": len(calls), "mosaic_max_batch": lead,
            "all_gather": len(re.findall(r" all-gather(?:-start)?\(", hlo)),
            "all_reduce": len(re.findall(r" all-reduce(?:-start)?\(", hlo))}


def train_phase(config, seqs_per_chip: int, peak_flops_per_chip: float,
                watch: CompileWatch):
    """13 steps of ``config`` through JaxTrainer.fit() on every local chip.
    Returns (what it measured, the compiled step's text); raises if the run
    did not train."""
    import jax

    from ray_tpu import train
    from ray_tpu.models import gpt2
    from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh
    from ray_tpu.parallel.train_state import (create_sharded_state,
                                              jit_train_step)

    n = len(jax.local_devices())
    global_batch = seqs_per_chip * n
    n_steps = WARMUP_STEPS + TIMED_STEPS
    tokens_per_step = global_batch * config.seq_len

    def train_loop():
        mesh = make_mesh(MeshSpec(data=n), jax.local_devices())
        optimizer = gpt2.make_optimizer(learning_rate=3e-4)
        t0 = time.perf_counter()
        params, opt_state = create_sharded_state(
            lambda key: gpt2.init_params(config, key),
            gpt2.logical_axes(config), mesh, jax.random.key(0), optimizer)
        jax.block_until_ready(params)
        init_s = time.perf_counter() - t0
        step = jit_train_step(gpt2.make_train_step(config, optimizer),
                              mesh=mesh)
        train.configure_profiler(
            flops_per_step=gpt2.flops_per_token(config) * tokens_per_step,
            tokens_per_step=tokens_per_step,
            peak_flops=peak_flops_per_chip * n)

        batches = train.get_dataset_shard("train").iter_batches(
            batch_size=global_batch, device_sharding=batch_sharding(mesh))
        seen = 0
        for i, batch in enumerate(batches):
            if i == WARMUP_STEPS:
                jax.block_until_ready(loss)
                in_window = watch.snapshot()
                t_window = time.perf_counter()
            if i == 0:
                first_args = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=x.sharding),
                    (params, opt_state, batch["tokens"], batch["targets"]))
            before, t0 = watch.snapshot(), time.perf_counter()
            params, opt_state, loss = step(
                params, opt_state, batch["tokens"], batch["targets"])
            if i == 0:
                jax.block_until_ready(loss)
                first_step_s = time.perf_counter() - t0
                first_compile = watch.since(before)
            # The loss stays on the device: reading it here would sync the
            # host to every step.
            train.report({"step": i, "loss": loss})
            seen += 1
        jax.block_until_ready(loss)
        window_s = time.perf_counter() - t_window
        window_compiles = watch.since(in_window)["compiles"]
        if seen != n_steps:
            raise RuntimeError(f"ingest gave {seen} batches, want {n_steps}")

        # Where everything lives: one copy of the state and one slice of the
        # batch on every chip.
        state_copies = {
            (len({s.device for s in leaf.addressable_shards}),
             all(s.data.shape == leaf.shape for s in leaf.addressable_shards))
            for leaf in jax.tree.leaves((params, opt_state))}
        batch_rows = sorted(
            (s.device.id, s.data.shape[0])
            for s in batch["tokens"].addressable_shards)
        memory = [d.memory_stats() for d in jax.local_devices()]
        rows = list(train.active_profiler().history)[WARMUP_STEPS:]

        # The same step, traced afresh for the first call's arguments (the
        # step's outputs carry other, equivalent sharding specs, which spell
        # another module), through the AOT route that TrainStep.anatomy()
        # takes (lower().compile()): it should find in the persistent cache the
        # executable the first call put there, and its text shows what each
        # chip really runs.
        before, t0 = watch.snapshot(), time.perf_counter()
        with jax.set_mesh(mesh):
            compiled = jax.jit(
                gpt2.make_train_step(config, optimizer), donate_argnums=(0, 1)
            ).lower(*first_args).compile()
        aot_s = time.perf_counter() - t0
        aot = watch.since(before)
        hlo = compiled.as_text()
        step_memory = compiled.memory_analysis()

        train.report({"step": n_steps, "summary": {
            "init_s": init_s,
            "first_step_s": first_step_s,
            "first_compile": first_compile,
            "window_s": window_s,
            "window_compiles": window_compiles,
            "state_copies": sorted(state_copies),
            "batch_rows": batch_rows,
            "bytes_in_use": [m and m.get("bytes_in_use") for m in memory],
            "peak_bytes_in_use": [m and m.get("peak_bytes_in_use")
                                  for m in memory],
            "peak_bytes_reserved": [m and m.get("peak_bytes_reserved")
                                    for m in memory],
            "step_memory": {
                k: getattr(step_memory, k + "_size_in_bytes")
                for k in ("argument", "output", "alias", "temp")},
            "data_wait_ms": 1e3 * float(np.mean([r["data_wait"] for r in rows])),
            "h2d_ms": 1e3 * float(np.mean([r["h2d"] for r in rows])),
            "aot_s": aot_s,
            "aot_cache": cache_verdict(aot),
            "hlo": hlo,
        }})

    dataset = token_dataset(config.vocab_size, config.seq_len, global_batch,
                            n_steps, seed=0)
    result = train.JaxTrainer(
        train_loop,
        scaling_config=train.ScalingConfig(
            num_workers=1, use_tpu=True, tpus_per_worker=n,
            worker_mode="threads"),
        datasets={"train": dataset},
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"JaxTrainer.fit() failed: {result.error!r}") \
            from result.error

    history = result.metrics_history
    summary = history[-1]["summary"]
    losses = [float(row["loss"]) for row in history[:-1]]
    if len(losses) != n_steps or not np.all(np.isfinite(losses)):
        raise RuntimeError(f"want {n_steps} finite losses, got {losses}")
    fall = losses[0] - losses[-1]
    if fall < MIN_LOSS_FALL:
        raise RuntimeError(
            f"loss fell {fall:.3f} over {n_steps} steps on a repeated batch "
            f"({losses[0]:.3f} -> {losses[-1]:.3f}), want >= {MIN_LOSS_FALL}")
    if summary["window_compiles"]:
        raise RuntimeError(
            f"{summary['window_compiles']} compile(s) inside the timed window")
    if summary["state_copies"] != [(n, True)]:
        raise RuntimeError(
            "state is not one full copy per chip: (devices, full-shape) = "
            f"{summary['state_copies']}")
    if summary["batch_rows"] != sorted((d.id, seqs_per_chip)
                                       for d in jax.local_devices()):
        raise RuntimeError(
            f"batch is not {seqs_per_chip} rows on each chip: "
            f"{summary['batch_rows']}")

    hlo = summary.pop("hlo")
    summary.update(custom_call_report(hlo))
    step_s = summary["window_s"] / TIMED_STEPS
    summary.update({
        "n_chips": n, "global_batch": global_batch, "losses": losses,
        "step_ms": 1e3 * step_s,
        "tokens_per_s_per_chip": tokens_per_step / step_s / n,
        "model_flops_utilization": gpt2.flops_per_token(config)
        * tokens_per_step / step_s / (peak_flops_per_chip * n),
    })
    log(f"[train] chips={n} batch={global_batch}x{config.seq_len} "
        f"loss {losses[0]:.3f} -> {losses[WARMUP_STEPS - 1]:.3f} (step "
        f"{WARMUP_STEPS}) -> {losses[-1]:.3f} (step {n_steps})")
    log(f"[train] set-up: init {summary['init_s']:.1f}s, first step "
        f"{summary['first_step_s']:.1f}s of which backend compile "
        f"{summary['first_compile']['compile_s']:.1f}s "
        f"(persistent cache: {cache_verdict(summary['first_compile'])}); "
        f"AOT lower+compile of the same step {summary['aot_s']:.1f}s "
        f"(persistent cache: {summary['aot_cache']})")
    log(f"[train] timed window: {TIMED_STEPS} steps, {summary['step_ms']:.1f} "
        f"ms/step, {summary['tokens_per_s_per_chip']:,.0f} tokens/s/chip, "
        f"{100 * summary['model_flops_utilization']:.1f}% of peak model "
        f"FLOP/s, compiles in window 0, data_wait "
        f"{summary['data_wait_ms']:.2f} ms/step, h2d "
        f"{summary['h2d_ms']:.2f} ms/step")
    log(f"[train] per chip: bytes_in_use={summary['bytes_in_use']} "
        f"peak_bytes_in_use={summary['peak_bytes_in_use']} "
        f"peak_bytes_reserved={summary['peak_bytes_reserved']}; the "
        f"compiled step's own account: {summary['step_memory']}")
    log(f"[train] compiled step: {summary['mosaic_calls']} Mosaic calls, "
        f"largest attention batch in one {summary['mosaic_max_batch']}, "
        f"{summary['all_reduce']} all-reduce, {summary['all_gather']} "
        f"all-gather")
    return summary, hlo


def check_train_on_chip(summary: dict, config, seqs_per_chip: int) -> None:
    """What only holds on the chip: memory statistics exist and are alike,
    and attention runs as Mosaic calls over each chip's own sequences."""
    in_use, peak = summary["bytes_in_use"], summary["peak_bytes_in_use"]
    if not all(in_use) or not all(peak):
        raise RuntimeError(
            f"memory_stats() is missing on a device: bytes_in_use={in_use} "
            f"peak_bytes_in_use={peak}")
    if max(in_use) > 1.1 * min(in_use):
        raise RuntimeError(f"chips hold unlike amounts: {in_use}")
    # attn_outside keeps the kernel's residuals: one forward and one fused
    # backward call per layer, no re-forward.
    if summary["mosaic_calls"] < 2 * config.n_layer:
        raise RuntimeError(
            f"compiled step has {summary['mosaic_calls']} Mosaic calls, want "
            f">= {2 * config.n_layer}: attention is not running as the splash "
            "kernel")
    if summary["mosaic_max_batch"] != seqs_per_chip:
        raise RuntimeError(
            f"a Mosaic call in the compiled step works on a batch of "
            f"{summary['mosaic_max_batch']}, want {seqs_per_chip} per chip: "
            "attention is not divided over the mesh")


# --------------------------------------------------------------- 4. kernels
def rel_err(a, b) -> float:
    import jax.numpy as jnp

    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def pallas_interpret_flags(fn, *args) -> list:
    """The ``interpret`` parameter of every pallas_call ``fn`` traces to."""
    import jax

    flags = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                flags.append(eqn.params["interpret"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return flags


def run_kernel(name: str, kernel_fn, reference_fn, args, tols,
               want_mosaic: int) -> dict:
    """Compile ``kernel_fn`` (returns a tuple of arrays), run it and its
    reference, and compare element by element of the tuple.  ``want_mosaic``
    is how many Mosaic calls the compiled module must hold."""
    import jax

    flags = pallas_interpret_flags(kernel_fn, *args)
    t0 = time.perf_counter()
    compiled = jax.jit(kernel_fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    mosaic = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    got = jax.block_until_ready(compiled(*args))
    want = jax.block_until_ready(jax.jit(reference_fn)(*args))
    errs = [rel_err(g, w) for g, w in zip(got, want)]
    row = {"kernel": name, "pallas_calls": len(flags),
           "interpret": [bool(f) for f in flags], "mosaic_calls": mosaic,
           "want_mosaic": want_mosaic,
           "compile_s": compile_s, "rel_err": errs, "tol": list(tols)}
    log(f"[kernels] {name}: {len(flags)} pallas_call(s) interpret="
        f"{sorted(set(row['interpret']))}, {mosaic} Mosaic call(s) compiled "
        f"in {compile_s:.1f}s, rel err "
        f"{', '.join(f'{e:.1e}' for e in errs)} (tol "
        f"{', '.join(f'{t:.0e}' for t in tols)})")
    bad = [(e, t) for e, t in zip(errs, tols) if not e <= t]
    if bad:
        raise RuntimeError(f"{name} disagrees with its XLA reference: {row}")
    return row


def kernels_phase(attn_shape, ring_shape, ssd_shape, kda_shape,
                  rope_shape, gdn_shape, conv_shape, gate_shape) -> list:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import layers, mamba2, shortconv
    from ray_tpu.ops import conv_kernel
    from ray_tpu.ops.attention import causal_attention, splash_attention
    from ray_tpu.ops.gdn import gdn, gdn_xla
    from ray_tpu.ops.kda import kda, kda_xla
    from ray_tpu.ops.ring_attention import ring_attention
    from ray_tpu.ops.ssd import ssd, ssd_xla
    from ray_tpu.parallel import MeshSpec, make_mesh

    def with_grads(fn):
        """fn's output and its input gradients under the cotangent ``out``
        (the gradient of sum(out**2) / 2)."""
        def run(*args):
            out, vjp = jax.vjp(fn, *args)
            return (out, *vjp(out))
        return run

    def normal(seed, shape):
        return jax.random.normal(jax.random.key(seed), shape, jnp.bfloat16)

    rows = []

    # splash attention, forward and gradient, against attn_impl="xla"
    qkv = [normal(i, attn_shape) for i in range(3)]
    rows.append(run_kernel(
        f"splash_attention fwd+grad {attn_shape}",
        with_grads(splash_attention),
        with_grads(lambda q, k, v: causal_attention(q, k, v, "xla")),
        qkv, (FWD_TOL, GRAD_TOL, GRAD_TOL, GRAD_TOL), want_mosaic=2))

    # fused ring block at world=1 against the einsum body.  The ring traces a
    # masked diagonal block and an unmasked full block; at world=1 the causal
    # ring compiles only the first (the switch between them folds away) and
    # the non-causal ring only the second, so both run here.
    mesh = make_mesh(MeshSpec(), jax.local_devices()[:1])

    def ring_with(impl, causal):
        return with_grads(lambda q, k, v: ring_attention(
            q, k, v, mesh=mesh, causal=causal, impl=impl))

    qkv = [normal(6 + i, ring_shape) for i in range(3)]
    for causal in (True, False):
        rows.append(run_kernel(
            f"fused ring attention world=1 causal={causal} fwd+grad "
            f"{ring_shape}",
            ring_with("fused", causal), ring_with("einsum", causal), qkv,
            (FWD_TOL, GRAD_TOL, GRAD_TOL, GRAD_TOL), want_mosaic=2))

    # the Mamba-2 scan's two kernels (``ssd`` takes them at this shape),
    # output and every gradient (x, delta, A, B, C, D), against the XLA
    # form; delta and A as a layer has them when training starts
    b, S, H, P, G, N, chunk = ssd_shape
    keys = jax.random.split(jax.random.key(9), 3)
    scan_args = (
        normal(10, (b, S, H, P)),
        jnp.exp(jax.random.uniform(keys[0], (b, S, H), minval=math.log(1e-3),
                                   maxval=math.log(0.1))),
        -jax.random.uniform(keys[1], (H,), minval=1.0, maxval=16.0),
        normal(11, (b, S, G, N)) * 0.5, normal(12, (b, S, G, N)) * 0.5,
        jax.random.normal(keys[2], (H,)))
    rows.append(run_kernel(
        f"ssd scan fwd+grad {ssd_shape}",
        with_grads(lambda *a: ssd(*a, chunk)),
        with_grads(lambda *a: ssd_xla(*a, chunk)), scan_args,
        (FWD_TOL,) + (GRAD_TOL,) * 6, want_mosaic=2))

    # the delta-rule scan's kernels (``kda`` takes them at this shape),
    # output and every gradient (q, k, v, g, beta), against the XLA form; q
    # and k of unit length a head, keys that share a part, g a head's own
    # rate in [1, 16] times a step in [0.001, 0.1]
    b, S, H, d, chunk = kda_shape
    keys = jax.random.split(jax.random.key(13), 5)

    def unit(key, shift, shape=None):
        x = jax.random.normal(key, shape or (b, S, H, d)) + shift
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True))

    delta_args = (
        (unit(keys[0], 0.0) * d ** -0.5).astype(jnp.bfloat16),
        unit(keys[1], 0.5).astype(jnp.bfloat16), normal(14, (b, S, H, d)),
        -jax.random.uniform(keys[2], (1, 1, H, 1), minval=1.0, maxval=16.0)
        * jnp.exp(jax.random.uniform(keys[3], (b, S, H, d),
                                     minval=math.log(1e-3),
                                     maxval=math.log(0.1))),
        2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (b, S, H))))
    rows.append(run_kernel(
        f"kda scan fwd+grad {kda_shape}",
        with_grads(lambda *a: kda(*a, chunk)),
        with_grads(lambda *a: kda_xla(*a, chunk)), delta_args,
        (FWD_TOL,) + (GRAD_TOL,) * 5, want_mosaic=4))

    # the same rule with one decay a head (``gdn`` takes its kernels at this
    # shape: keys and values off the lane tiles), drawn the same way
    b, S, H, dk, dv, chunk = gdn_shape
    keys = jax.random.split(jax.random.key(15), 5)
    delta_args = (
        (unit(keys[0], 0.0, (b, S, H, dk)) * dk ** -0.5).astype(jnp.bfloat16),
        unit(keys[1], 0.5, (b, S, H, dk)).astype(jnp.bfloat16),
        normal(16, (b, S, H, dv)),
        -jax.random.uniform(keys[2], (1, 1, H), minval=1.0, maxval=16.0)
        * jnp.exp(jax.random.uniform(keys[3], (b, S, H),
                                     minval=math.log(1e-3),
                                     maxval=math.log(0.1))),
        2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (b, S, H))))
    rows.append(run_kernel(
        f"gdn scan fwd+grad {gdn_shape}",
        with_grads(lambda *a: gdn(*a, chunk)),
        with_grads(lambda *a: gdn_xla(*a, chunk)), delta_args,
        (FWD_TOL,) + (GRAD_TOL,) * 5, want_mosaic=4))

    # the rotary pass's kernel over q and k in one call (``rope`` takes it
    # at this shape), values and gradients, against the product an array
    b, S, heads, hd, rotary = rope_shape

    def turned(turn):
        def run(*xs):
            out, vjp = jax.vjp(lambda *xs: turn(xs), *xs)
            return (*out, *vjp(out))
        return run

    rows.append(run_kernel(
        f"rope fwd+grad {rope_shape}",
        turned(lambda xs: layers.rope(xs, 5e5, rotary, first=True)),
        turned(lambda xs: tuple(
            layers._rope_product(x, 5e5, rotary, False, True) for x in xs)),
        [normal(20 + i, (b, S, H, hd)) for i, H in enumerate(heads)],
        (FWD_TOL,) * 2 + (GRAD_TOL,) * 2, want_mosaic=2))

    # the short causal convolution's pass (``ops/conv_kernel.py``), values
    # and the gradients of x, the taps and the bias, against
    # ``causal_conv`` under its silu: a span inside the projection's
    # output that leaves as three arrays, a call each way each
    b, S, full, offset, widths, taps = conv_shape
    span = sum(widths)
    conv_args = [normal(30, (b, S, full)),
                 jax.random.normal(jax.random.key(31), (taps, span)) * 0.5,
                 jax.random.normal(jax.random.key(32), (span,))]
    cuts = [sum(widths[:i]) for i in range(1, len(widths))]
    rows.append(run_kernel(
        f"causal conv fwd+grad {conv_shape}",
        turned(lambda a: conv_kernel.conv(
            *a, act=True, out_dtype=jnp.bfloat16, offset=offset,
            widths=widths)),
        turned(lambda a: tuple(jnp.split(jax.nn.silu(mamba2.causal_conv(
            a[0][..., offset:offset + span], a[1], a[2])).astype(
                jnp.bfloat16), cuts, axis=-1))),
        conv_args, (FWD_TOL,) * len(widths) + (GRAD_TOL,) * 3,
        want_mosaic=2 * len(widths)))

    # its gated form over ``[B | C | u]`` against ``gated_conv``
    b, S, width, taps = gate_shape
    rows.append(run_kernel(
        f"gated conv fwd+grad {gate_shape}",
        turned(lambda a: (conv_kernel.gated(*a),)),
        turned(lambda a: (shortconv.gated_conv(*a),)),
        [normal(33, (b, S, 3 * width)),
         jax.random.normal(jax.random.key(34), (taps, width)) * 0.5],
        (FWD_TOL,) + (GRAD_TOL,) * 2, want_mosaic=2))
    return rows


def check_kernels_on_chip(rows: list) -> None:
    """interpret=False is asserted, not inferred from the backend's name."""
    for row in rows:
        if not row["pallas_calls"] or any(row["interpret"]):
            raise RuntimeError(f"kernel ran interpreted or not at all: {row}")
        if row["mosaic_calls"] != row["want_mosaic"]:
            raise RuntimeError(
                f"compiled module holds {row['mosaic_calls']} Mosaic calls, "
                f"want {row['want_mosaic']}: {row}")


# --------------------------------------------------------------------- main
def main() -> int:
    t_start = time.perf_counter()
    devices = device_phase()

    import ray_tpu
    from ray_tpu._private.accelerators import device_peaks
    from ray_tpu.models import gpt2
    from ray_tpu.parallel.compile_cache import configure_compile_cache

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices)}
    peaks = device_peaks(d.device_kind)
    cache_dir = configure_compile_cache()
    log(f"[device] peaks {peaks}; compile cache at {cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'not set'})")
    watch = CompileWatch()

    # GPTConfig's defaults are GPT-2 124M: 12 layers, d_model 768, 12 heads of
    # 64, vocab 50304, S=1024, bf16.
    config = gpt2.GPTConfig(remat_policy="attn_outside", scan_layers=False)
    try:
        runtime = runtime_phase()
        train, hlo = train_phase(config, SEQS_PER_CHIP, peaks.flops, watch)
        check_train_on_chip(train, config, SEQS_PER_CHIP)
        kernels = kernels_phase(
            attn_shape=(2, config.seq_len, config.n_head, config.head_dim),
            ring_shape=(1, 8192, 8, 128),  # the BENCH_RING.json shape
            # a Mamba-2 layer's scan in ``nemotron-ep16-s8192``: rows,
            # positions, heads, head_dim, groups, state, chunk
            ssd_shape=(2, 8192, 64, 64, 8, 128, 128),
            # a KDA layer's scan in ``solar-open2-ep40-tp8``: rows,
            # positions, heads, head_dim, chunk
            kda_shape=(1, 8192, 8, 128, 64),
            # a full layer's q and k in ``laguna-ep32-s8192``: rows,
            # positions, the two arrays' heads, head_dim, the lanes that turn
            rope_shape=(1, 8192, (48, 8), 128, 64),
            # a gated-delta-net layer's scan in ``olmo-hybrid-s8192``: rows,
            # positions, heads, a head's keys, its values, chunk
            gdn_shape=(1, 8192, 30, 96, 192, 64),
            # a Mamba-2 layer's convolution in ``nemotron-ep16-s8192``: rows,
            # positions, the projection's width, where ``xBC`` starts in
            # it, ``xs | B | C``, taps
            conv_shape=(2, 8192, 10304, 4096, (4096, 1024, 1024), 4),
            # a convolution layer's gate in ``lfm2-ep4-s8192``: rows,
            # positions, a gate's width, taps
            gate_shape=(2, 8192, 2048, 3))
        check_kernels_on_chip(kernels)
    finally:
        ray_tpu.shutdown()

    total = watch.snapshot()
    log(f"[done] {time.perf_counter() - t_start:.0f}s; jax built or loaded "
        f"{total['compiles']} executables in {total['compile_s']:.1f}s, "
        f"persistent cache {total['hits']} hits / {total['misses']} misses")
    os.makedirs(REPORT_DIR, exist_ok=True)
    with open(os.path.join(REPORT_DIR, "chip_smoke.json"), "w") as f:
        json.dump({"device": device, "runtime": runtime, "train": train,
                   "kernels": kernels, "compile_totals": total,
                   "compile_cache_dir": cache_dir}, f, indent=1, default=str)
    with open(os.path.join(REPORT_DIR, "chip_smoke_step.hlo.txt"), "w") as f:
        f.write(hlo)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
