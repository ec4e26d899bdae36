"""Traffic kind ``train``: one cell, one run, through the entry points a user
calls: ``ray_tpu.init()`` -> ``JaxTrainer.fit()`` -> streaming ingest ->
``jit_train_step``.

Set-up ends after the warm-up steps, each fenced.  The window starts and ends
at a ``block_until_ready``; inside it the loop hands the loss to
``train.report`` as a device array and fences only on the loss of
``STEPS_AHEAD`` steps back, so the host never runs further ahead than that
and never idles the device while its own work per step is shorter than a
step.  It stops dispatching when ``--seconds`` have passed; the steps then in
flight are part of the window, which ends when the last of them has.

The batches are a stream one epoch long, or, where the traffic file states a
corpus (``dataset_batches``), the program's ingest epoch after epoch: the same
rows on every run, each epoch in the order ``--seed`` gives it.
"""

from __future__ import annotations

import collections
import glob
import os
import shutil
import sys
import tempfile
import time
from typing import Dict

import numpy as np

from benchmarks.lib import correct, spec, state, traffic as traffic_lib
from benchmarks.lib import anatomy, trace_reduce
from benchmarks.lib.compile_watch import CompileWatch, hlo_report
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.record import RunRecord

#: The host may run this many steps ahead of the device.  ISSUE 22 asked for
#: two; in one bad ten minutes on the chip machine's shared host, 4 of 12 runs
#: of mistral7b-s1024 then lost 3 to 6 steps (0.8-1.6 s) to host stalls longer
#: than two 265 ms steps, with the device's own step time unchanged (PERF.md,
#: Findings, PR 22).  Eight steps are 2.1 s of slack there; the state is
#: donated from step to step, so the depth costs no device memory.
STEPS_AHEAD = 8
#: A traced run starts the profiler after this many window steps.
TRACE_AFTER = 3


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(cell: Dict, args, t_process: float) -> Dict:
    #: seconds from process start to each milestone of set-up, for the report
    marks: Dict[str, float] = {}

    def mark(name: str) -> None:
        marks[name] = time.time() - t_process

    mark("start")
    import jax

    config, traffic = cell["config_file"], cell["traffic_file"]
    chips = cell["chips"]
    if args.rehearse and "rehearse_with" in config:
        config = dict(spec.load_json(spec.BENCH_DIR, "configs",
                                     config["rehearse_with"] + ".json"),
                      layout=config["layout"])
    platform = jax.default_backend()
    local = jax.local_devices()
    mark("jax_backend_up")
    if not args.rehearse and platform != "tpu":
        raise SystemExit(
            f"the benchmark needs a TPU and jax found backend {platform!r} "
            f"with devices {local}; --rehearse runs a tiny preset for counts")
    if len(local) < chips:
        raise SystemExit(f"cell {cell['name']} needs {chips} chips, jax "
                         f"found {len(local)}: {local}")
    devices = local[:chips]
    peaks = peaks_for(devices[0].device_kind) if platform == "tpu" else None

    # The program's spill and session directories default to fixed paths
    # under /tmp; whatever a run writes has to stay under its own TMPDIR.
    scratch = os.path.join(tempfile.gettempdir(), "ray_tpu_bench")
    os.environ.setdefault("RAY_TPU_SPILL_DIR", os.path.join(scratch, "spill"))
    os.environ.setdefault("RAY_TPU_SESSION_DIR",
                          os.path.join(scratch, "session"))
    import ray_tpu
    from ray_tpu import train
    from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh
    from ray_tpu.parallel.compile_cache import configure_compile_cache
    from ray_tpu.parallel.mesh import pytree_sharding
    from ray_tpu.parallel.train_state import (create_sharded_state,
                                              jit_train_step)

    # Every program goes to the persistent cache, however quickly it
    # compiled, so that a cell's second run in a checkout compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_dir = configure_compile_cache()
    watch = CompileWatch()

    seq_len = traffic["seq_len"]
    if args.rehearse:
        seq_len = min(seq_len, config.get("rehearse_seq_len", seq_len))
    mesh_axes = config["layout"]["mesh"]
    if int(np.prod(list(mesh_axes.values()))) != chips:
        raise SystemExit(f"layout {mesh_axes} is not {chips} chips")
    family = spec.load_module("models", config["family"]).build(config,
                                                                seq_len)
    seqs_per_chip = traffic["seqs_per_chip"]
    global_batch = seqs_per_chip * chips
    warmup = traffic["warmup_steps"]
    n_batches = warmup + 8 + int(args.seconds
                                 * traffic["max_steps_per_second"])
    gen = traffic_lib.make(traffic, vocab_size=family.vocab_size,
                           eod_id=family.eod_id, global_batch=global_batch,
                           seq_len=seq_len, seed=args.seed)
    record = RunRecord(
        cell=cell, chips=chips, peaks=peaks,
        tokens_per_step=global_batch * seq_len,
        flops_per_step=family.flops_per_token * global_batch * seq_len,
        seq_len=seq_len, attention_calls=family.attention_calls)
    out: Dict = {"device_count": len(local), "marks": marks}
    trace_dir = os.path.join(args.out_dir, "trace",
                             f"{cell['name']}-seed{args.seed}")
    log(f"{cell['name']}: {config['family']} {mesh_axes} on {chips} x "
        f"{devices[0].device_kind}, batch {global_batch} x {seq_len}, "
        f"{family.flops_per_token / 1e9:.3f} GFLOP/token, compile cache "
        f"{cache_dir}")

    def train_loop():
        mark("train_loop_entered")
        mesh = make_mesh(MeshSpec(**mesh_axes), devices)
        expected = pytree_sharding(family.logical_axes, mesh)
        inner = family.make_optimizer()
        optimizer = state.born_sharded(inner, expected)
        out["state_sharded_by"] = "the program" if optimizer is inner else \
            "the harness (benchmarks/lib/state.py), not yet the program"
        t0 = time.perf_counter()
        params, opt_state = create_sharded_state(
            family.init_fn, family.logical_axes, mesh,
            jax.random.key(args.seed), optimizer)
        jax.block_until_ready((params, opt_state))
        record.init_state_s = time.perf_counter() - t0
        mark("state_ready")
        step = jit_train_step(family.make_train_step(optimizer), mesh=mesh)
        shard = train.get_dataset_shard("train")

        def epochs():
            # A stream is one epoch long; a corpus (``dataset_batches`` in
            # the traffic file) is trained on epoch after epoch, each call
            # of ``iter_batches`` the ingest's next epoch in its own order.
            while True:
                yield from shard.iter_batches(
                    batch_size=global_batch,
                    device_sharding=batch_sharding(mesh))
                if getattr(gen, "dataset_batches", None) is None:
                    return

        batches = epochs()
        losses = []

        def one_step(i, spans=None):
            t = [time.perf_counter()]
            with jax.profiler.TraceAnnotation("bench.next_batch"):
                batch = next(batches, None)
            if batch is None:
                raise RuntimeError(
                    f"the ingest ran dry at step {i} of {n_batches} batches")
            t.append(time.perf_counter())
            nonlocal params, opt_state
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                params, opt_state, loss = step(
                    params, opt_state, batch["tokens"], batch["targets"])
            t.append(time.perf_counter())
            with jax.profiler.TraceAnnotation("bench.report"):
                train.report({"step": i, "loss": loss})
            t.append(time.perf_counter())
            losses.append(loss)
            if spans is not None:
                for name, a, b in zip(("next_batch", "dispatch", "report"),
                                      t, t[1:]):
                    spans.setdefault(name, []).append(b - a)
            return batch, loss

        for i in range(warmup):
            batch, loss = one_step(i)
            jax.block_until_ready(loss)
            mark(f"warmup_step_{i}")
        record.compile_setup = watch.snapshot()
        out["setup_s"] = time.time() - t_process

        # ------------------------------------------------------- the window
        before = watch.snapshot()
        in_flight = collections.deque()
        tracing, traced = False, 0
        spans = record.host_spans
        t_window = time.perf_counter()
        try:
            while (time.perf_counter() - t_window < args.seconds) or tracing:
                if args.trace and not traced and record.steps == TRACE_AFTER:
                    shutil.rmtree(trace_dir, ignore_errors=True)
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=options)
                    tracing = True
                if args.rehearse and record.steps == n_batches - warmup:
                    break  # a tiny preset outruns max_steps_per_second
                batch, loss = one_step(warmup + record.steps, spans)
                record.steps += 1
                in_flight.append(loss)
                if len(in_flight) > STEPS_AHEAD:
                    t0 = time.perf_counter()
                    with jax.profiler.TraceAnnotation("bench.fence"):
                        jax.block_until_ready(in_flight.popleft())
                    spans.setdefault("fence", []).append(
                        time.perf_counter() - t0)
                if tracing:
                    traced += 1
                    if traced == traffic["trace_steps"]:
                        # The host is steps ahead: let the device finish what
                        # was traced.  The drain that follows lies after the
                        # last start of the step, outside the stretch used.
                        with jax.profiler.TraceAnnotation("bench.trace_drain"):
                            jax.block_until_ready(loss)
                        in_flight.clear()
                        jax.profiler.stop_trace()
                        tracing = False
            jax.block_until_ready(loss)
        except Exception as e:  # noqa: BLE001 — a failed step is a result
            log(f"step {warmup + record.steps} raised {e!r}")
            out["raised"] = repr(e)
        finally:
            if tracing:
                jax.profiler.stop_trace()
        record.window_s = time.perf_counter() - t_window
        record.compile_window = watch.since(before)
        record.memory = [d.memory_stats() or {} for d in devices]
        record.profiler_rows = list(
            train.active_profiler().history)[-record.steps:]
        if "raised" in out:
            return
        out["losses"] = [float(x) for x in jax.device_get(losses)]

        # ------------------------------------- outside the window: correct
        out["placement"] = correct.placement(
            params, opt_state, batch["tokens"], expected, seqs_per_chip,
            chips)
        if args.trace:
            # The same step through lower().compile(): it finds in the
            # persistent cache what the first call put there, and its text
            # and memory account show what each chip really runs.
            first_args = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding),
                (params, opt_state, batch["tokens"], batch["targets"]))
            with jax.set_mesh(mesh):
                compiled = jax.jit(
                    family.make_train_step(optimizer), donate_argnums=(0, 1)
                ).lower(*first_args).compile()
            text = compiled.as_text()
            record.hlo = hlo_report(text)
            names = anatomy.of_text(text)
            if names is not None:
                record.anatomy = names
            memory = compiled.memory_analysis()
            record.step_memory = {
                k: getattr(memory, k + "_size_in_bytes")
                for k in ("argument", "output", "alias", "temp")}
        del opt_state  # room for the reference's gradients
        n_check = mesh.shape["data"] * mesh.shape["fsdp"]
        rows = gen.check_rows(n_check)
        tokens, targets = (jax.device_put(a, batch_sharding(mesh))
                           for a in (rows[:, :-1], rows[:, 1:]))
        limits = config.get("check", {})
        out["reference"] = correct.compare(family, params, tokens, targets,
                                           mesh, limits.get("loss_tol"))
        if "seed_grad_tol" in limits:
            del params  # room for the seed's
            out["reference_at_seed"] = correct.at_the_seed(
                family, mesh, args.seed, rows, limits["seed_grad_tol"])

    mark("imports_done")
    if platform == "tpu":
        ray_tpu.init()
    else:
        ray_tpu.init(num_tpus=chips)
    mark("ray_tpu_init")
    try:
        result = train.JaxTrainer(
            train_loop,
            scaling_config=train.ScalingConfig(
                num_workers=1, use_tpu=True, tpus_per_worker=chips,
                worker_mode="threads"),
            dataset_config=train.DatasetConfig(shuffle_seed=args.seed),
            datasets={"train": gen.dataset(n_batches)},
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise RuntimeError(f"JaxTrainer.fit() failed: {result.error!r}") \
            from result.error

    if args.trace:
        found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if found:
            record.trace = trace_reduce.load(found[0])
            if record.trace.first:
                modules = record.trace.first.modules
                record.step_module = trace_reduce.step_module(modules)
                record.steady = trace_reduce.steady_window(
                    modules, record.step_module)
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return finish(cell, args, record, out, gen, devices)


def finish(cell, args, record: RunRecord, out: Dict, gen, devices) -> Dict:
    """From what the run recorded to the result line and the report."""
    traffic = cell["traffic_file"]
    losses = np.asarray(out.get("losses", []), dtype=np.float64)
    finite = bool(len(losses)) and bool(np.all(np.isfinite(losses)))
    n_window = record.steps
    failed = int(np.sum(~np.isfinite(losses[-n_window:]))) if len(losses) \
        else 0
    failed += 1 if "raised" in out else 0
    fall = float(losses[0] - np.mean(losses[-5:])) if finite else float("nan")
    checks = {
        "reference": bool(out.get("reference", {}).get("ok")
                          and out.get("reference_at_seed", {"ok": 1})["ok"]),
        "losses_finite": finite,
        "loss_fell": bool(finite and (args.rehearse
                                      or fall >= traffic["min_loss_fall"])),
        "placement": bool(out.get("placement", {}).get("ok")),
        "no_failed_step": failed == 0,
    }

    ref, at_seed = out.get("reference"), out.get("reference_at_seed")
    if ref:  # every number compared, beside its limit
        log(f"correct: loss error {ref['loss_err']:.3g} at S={ref['seq_len']}"
            f" and {ref['grad_loss_err']:.3g} at S={ref['grad_seq_len']} "
            f"(limit {ref['loss_tol']:g}), largest gradient-leaf error "
            f"{ref['grad_err_max']:.3g} (limit {ref['grad_tol']:g})"
            + (f"; at the seed's parameters the leaves' median "
               f"{at_seed['grad_norm_err_median']:.3g} (limit "
               f"{at_seed['seed_grad_tol']:g}) and largest "
               f"{at_seed['grad_norm_err_max']:.3g} (limit "
               f"{at_seed['leaf_tol']:g})" if at_seed else "")
            + f"; loss fell {fall:.3g} (at least "
            f"{traffic['min_loss_fall']:g}), placement "
            f"{out.get('placement', {}).get('wrong') or 'ok'} (state "
            f"sharded by {out.get('state_sharded_by')}), failed steps "
            f"{failed} (limit 0)")

    rate = record.steps * record.tokens_per_step / record.window_s \
        / record.chips if record.window_s else 0.0
    values = {"tokens_per_s_per_chip": rate, "setup_s": out.get("setup_s")}
    group = "per_layer" if args.trace else "end_to_end"
    metrics, notes = {}, {}
    for m in cell["metrics"][group]:
        if args.trace:
            reader = spec.load_module("layer_metrics", m["name"])
            value = reader.read(record)
            if hasattr(reader, "describe"):  # more for the report, optional
                notes[m["name"]] = reader.describe(record)
        else:
            value = values.get(m["name"])
        # A rehearsal prints counts, never a time, a rate or a utilization.
        if value is not None and (not args.rehearse or m["unit"] == "count"):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if args.rehearse and not args.trace:
        metrics["steps"] = {"value": record.steps, "unit": "count"}

    # The runtime's own account, the same in traced and untraced runs: what
    # lives on the fullest chip after the window plus the most it reserved
    # for a program's temporaries.  (device.peak_hbm is the compiler's.)
    mem_peak = max((m.get("bytes_in_use", 0) + m.get("peak_bytes_reserved", 0)
                    for m in record.memory), default=0)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": out["device_count"], "memory_peak_bytes": mem_peak}
    line = {"correct": all(checks.values()), "attempted": record.steps,
            "failed": failed, "metrics": metrics, "device": device}
    if record.steady and devices[0].platform == "tpu":
        lo, hi, n_steps, _ = record.steady
        busy = {ordinal: trace_reduce.busy_seconds(d.ops, lo, hi)
                for ordinal, d in record.trace.devices.items()}
        device["busy_s"] = float(np.mean(list(busy.values())))
        device["window_s"] = hi - lo
        idlest = record.trace.devices[min(busy, key=busy.get)]
        line["breakdown"] = {
            "device_ops": [[n, s / n_steps] for n, s in trace_reduce.top_ops(
                record.trace.first.ops, lo, hi)],
            "idle_gaps": [list(g) for g in trace_reduce.label_gaps(
                trace_reduce.idle_gaps(idlest.ops, lo, hi),
                record.trace.host_spans)]}

    report = {
        "cell": cell["name"], "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "rehearse": args.rehearse, "line": line,
        "metric_notes": notes,
        "checks": checks, "loss_first": float(losses[0]) if finite else None,
        "loss_last5": float(np.mean(losses[-5:])) if finite else None,
        "loss_fall": fall, "losses": [float(x) for x in losses],
        "state_sharded_by": out.get("state_sharded_by"),
        "reference": out.get("reference"),
        "reference_at_seed": out.get("reference_at_seed"),
        "placement": out.get("placement"),
        "raised": out.get("raised"), "steps": record.steps,
        "setup_marks_s": out.get("marks"),
        "window_s": record.window_s, "init_state_s": record.init_state_s,
        "compile_setup": record.compile_setup,
        "compile_window": record.compile_window, "memory": record.memory,
        "step_memory": record.step_memory, "hlo": record.hlo,
        "traffic_drawn": gen.describe(),
        "host_span_medians_ms": {
            k: 1e3 * float(np.median(v))
            for k, v in record.host_spans.items()},
        # where a window lost time: the longest spans, as [step, ms]
        "host_span_longest_ms": {
            k: [[int(i), 1e3 * v[i]] for i in np.argsort(v)[::-1][:5]]
            for k, v in record.host_spans.items()},
        "profiler_bucket_totals_s": {
            b: sum(r[b] for r in record.profiler_rows)
            for b in ("data_wait", "h2d", "collective", "ckpt_block")},
        "trace_layout": record.trace.layout if record.trace else None,
        "step_module": record.step_module,
        "steady": list(record.steady[:3]) if record.steady else None,
    }
    return {"line": line, "report": report}
