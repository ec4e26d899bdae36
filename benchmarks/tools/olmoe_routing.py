"""How often the program's routing differs from the float32 reference's, and
how unevenly the experts are loaded after some training on the cell's
traffic:

    python3 benchmarks/tools/olmoe_routing.py [--cell olmoe-s4096] [--seed N]
                                              [--steps 60] [--tiny]

Trains the cell's configuration for ``--steps`` steps on the cell's traffic
(the program's jitted train step, no trainer, no window), then runs the
program's loss and the plain reference on the same check row and compares,
per layer, which experts each token chose: the share of (token, slot) pairs
whose expert the other side did not choose, and the per-expert load (rows
of the largest group over the mean).  Top-k is discontinuous, so a bf16
residual stream under a float32 router can flip a token's k-th expert; this
says how often it does.  The program's choices are read by wrapping
``models/moe.route`` in a ``jax.debug.callback`` here, in this tool: the
program has no hook for it.  ``--tiny`` runs the rehearsal preset on
whatever backend jax finds.  Prints one JSON object.
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
    parser.add_argument("--cell", default="olmoe-s4096")
    parser.add_argument("--seed", type=int, default=2147483999)
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()

    import jax
    import numpy as np

    from benchmarks.lib import spec, traffic as traffic_lib
    from benchmarks.reference import olmoe as reference
    from ray_tpu.models import moe

    cell = spec.load_cell(spec.load_benchmark(), args.cell)
    config, traffic = cell["config_file"], cell["traffic_file"]
    seq_len = traffic["seq_len"]
    if args.tiny:
        config = spec.load_json(spec.BENCH_DIR, "configs",
                                config["rehearse_with"] + ".json")
        seq_len = config["rehearse_seq_len"]
    family = spec.load_module("models", config["family"]).build(config,
                                                                seq_len)
    batch = traffic["seqs_per_chip"]
    gen = traffic_lib.make(traffic, vocab_size=family.vocab_size,
                           eod_id=family.eod_id, global_batch=batch,
                           seq_len=seq_len, seed=args.seed)

    optimizer = family.make_optimizer()
    params = jax.jit(family.init_fn)(jax.random.key(args.seed))
    opt_state = jax.jit(optimizer.init)(params)
    step = jax.jit(family.make_train_step(optimizer), donate_argnums=(0, 1))
    losses = []
    for i in range(args.steps):
        rows = gen.batch(i)
        params, opt_state, loss = step(params, opt_state, rows["tokens"],
                                       rows["targets"])
        losses.append(float(loss))
    del opt_state

    seen = []
    route = moe.route

    def spy(*a, **kw):
        weights, experts, aux = route(*a, **kw)
        jax.debug.callback(lambda e: seen.append(np.asarray(e)), experts)
        return weights, experts, aux

    moe.route = spy
    try:
        row = gen.check_rows(1)
        tokens, targets = row[:, :-1], row[:, 1:]
        system = float(jax.jit(family.loss_fn)(params, tokens, targets))
        jax.effects_barrier()
    finally:
        moe.route = route
    with jax.default_matmul_precision("highest"):
        _, _, _, chosen = jax.jit(
            lambda p, t: reference.run(p, t, config))(params, tokens)
    chosen = np.asarray(chosen)                      # (L, T, E) bool
    E, k = config["num_experts"], config["num_experts_per_tok"]
    flips, loads = [], []
    for layer, experts in enumerate(seen):           # (T, k) ids
        experts = experts.reshape(-1, k)
        agreed = np.take_along_axis(chosen[layer], experts, axis=1)
        flips.append(float(1.0 - agreed.mean()))
        counts = np.bincount(experts.reshape(-1), minlength=E)
        loads.append({"max_over_mean": float(counts.max() / counts.mean()),
                      "min": int(counts.min()), "max": int(counts.max()),
                      "empty": int((counts == 0).sum())})
    device = jax.devices()[0]
    print(json.dumps({
        "cell": args.cell, "tiny": args.tiny, "seed": args.seed,
        "steps": args.steps, "loss_first": losses[0], "loss_last": losses[-1],
        "tokens": int(tokens.size), "system_loss": system,
        "flipped_pair_share_by_layer": flips,
        "expert_load_by_layer": loads,
        "device": {"platform": device.platform, "kind": device.device_kind}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
