"""What share of a step's (position, expert) pairs lands on the experts this
chip holds, per layer, in a cell whose expert layers hold a share:

    python3 benchmarks/tools/sdar_routing.py [--cell sdar-ep8-s8192]
        --seeds 1 2 3 ... [--steps 40] [--tiny]

For each seed, in one process: the parameters the family's ``init_fn`` gives
for that seed (the configuration's ``init_seed`` where it states one), then
``--steps`` steps of the program's jitted train step on the cell's traffic
drawn from the seed (no trainer, no window), then one more step's routing:
per layer, the held experts' pairs over all positions x experts a token.
The megablox kernels visit the held groups' tiles only, so that share is
what the held products' time follows, and its wander from run to run is the
wander of ``tokens_per_s_per_chip`` (PERF.md, PR 34).  The program's choices
are read by wrapping ``models/moe.route`` in a ``jax.debug.callback`` here:
the program has no hook for it.  One JSON line a seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cell", default="sdar-ep8-s8192")
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()

    import jax
    import numpy as np

    from benchmarks.lib import spec, traffic as traffic_lib
    from ray_tpu.models import moe
    from ray_tpu.parallel.compile_cache import configure_compile_cache

    configure_compile_cache()
    cell = spec.load_cell(spec.load_benchmark(), args.cell)
    config, traffic = cell["config_file"], cell["traffic_file"]
    seq_len = traffic["seq_len"]
    if args.tiny:
        config = spec.load_json(spec.BENCH_DIR, "configs",
                                config["rehearse_with"] + ".json")
        seq_len = config["rehearse_seq_len"]
    family = spec.load_module("models", config["family"]).build(config,
                                                                seq_len)
    first, stop = config["experts_held"]
    optimizer = family.make_optimizer()
    step = jax.jit(family.make_train_step(optimizer), donate_argnums=(0, 1))

    seen = []
    route = moe.route

    def spy(*a, **kw):
        weights, experts, aux = route(*a, **kw)
        jax.debug.callback(lambda e: seen.append(np.asarray(e)), experts)
        return weights, experts, aux

    device = jax.devices()[0]
    for seed in args.seeds:
        gen = traffic_lib.make(
            traffic, vocab_size=family.vocab_size, eod_id=family.eod_id,
            global_batch=traffic["seqs_per_chip"], seq_len=seq_len, seed=seed)
        params = jax.jit(family.init_fn)(jax.random.key(seed))
        opt_state = jax.jit(optimizer.init)(params)
        losses = []
        for i in range(args.steps):
            rows = gen.batch(i)
            params, opt_state, loss = step(params, opt_state, rows["tokens"],
                                           rows["targets"])
            losses.append(float(loss))
        del opt_state
        seen.clear()
        moe.route = spy
        try:
            rows = gen.batch(args.steps)
            jax.jit(family.loss_fn)(params, rows["tokens"], rows["targets"])
            jax.effects_barrier()
        finally:
            moe.route = route
        shares = [float(np.mean((e >= first) & (e < stop))) for e in seen]
        print(json.dumps({
            "cell": args.cell, "tiny": args.tiny, "seed": seed,
            "init_seed": config.get("init_seed"), "steps": args.steps,
            "loss_first": losses[0] if losses else None,
            "loss_last": losses[-1] if losses else None,
            "even_share": (stop - first) / config["num_experts_published"],
            "held_share_by_layer": shares,
            "device": {"platform": device.platform,
                       "kind": device.device_kind}}), flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
