"""The readings ``seed_grad_tol`` of a configuration's ``check`` is set from,
on the chip and at the cell's own size, in one process:

    python3 benchmarks/tools/control.py <cell> --seeds 1 2 3 ... [--controls 3]

For each seed: the parameters the seed gives and the cell's check rows, then
``lib/correct.py:at_the_seed`` on the program (what every run of the cell
reports for that seed: nothing here depends on a window) and, on the first
``--controls`` seeds, on the control in the program's place.

**The control** is the plain reference itself, computed on weights rounded to
an 8-bit float (4 exponent and 3 mantissa bits, scaled by each leaf's largest
value, the gradient passed straight through): the step below the bfloat16 the configurations
state, and under ``fsdp`` the one that tempts, since it would halve the bytes
of every weight gather.  It wraps the reference from outside; the reference
files know nothing of it.  One JSON line a seed, and at the end the largest
the program read and the smallest the control read: the limit has to lie
between the two, with room on both sides.  The benchmark's own runs never run
the control.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

NUMBERS = ("grad_loss_err", "grad_norm_err_median", "grad_norm_err_max")


def rounded(params):
    """Every matrix of ``params`` rounded to an 8-bit float (4 exponent and 3
    mantissa bits, scaled so that the leaf's largest value is the format's,
    240), the gradient passed straight through; vectors (norms, biases) as
    they are.  ``lax.reduce_precision`` and not a cast there and back: on the
    TPU the compiler drops such a pair of converts as excess precision (the
    first four-chip readings of this control came out as the reference itself,
    2.7e-6; my chip run, PR 30)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def one(a):
        if a.ndim < 2:
            return a
        scale = jnp.max(jnp.abs(a)) / 240.0
        near = lax.reduce_precision(a / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale
        return a + lax.stop_gradient(near - a)

    return jax.tree.map(one, params)


def control(family):
    """``family`` with the control in the program's place."""
    from benchmarks.lib import correct

    def loss_fn(params, tokens, targets):
        S = tokens.shape[1]
        return family.reference_loss(
            rounded(params), tokens, targets,
            S if S <= correct.GRAD_SEQ else correct.Q_BLOCK)

    return dataclasses.replace(family, loss_fn=loss_fn)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("cell")
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--controls", type=int, default=3,
                        help="the control runs on this many of the seeds")
    args = parser.parse_args()

    import jax

    from benchmarks.lib import correct, spec, traffic as traffic_lib
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.parallel.compile_cache import configure_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    configure_compile_cache()
    cell = spec.load_cell(spec.load_benchmark(), args.cell)
    config, traffic = cell["config_file"], cell["traffic_file"]
    limit = config.get("check", {}).get("seed_grad_tol")
    if limit is None:
        raise SystemExit(f"{cell['config']} states no seed_grad_tol")
    if len(jax.local_devices()) < cell["chips"]:
        raise SystemExit(f"{args.cell} needs {cell['chips']} chips")
    family = spec.load_module("models", config["family"]).build(
        config, traffic["seq_len"])
    mesh = make_mesh(MeshSpec(**config["layout"]["mesh"]),
                     jax.local_devices()[:cell["chips"]])
    n_check = mesh.shape["data"] * mesh.shape["fsdp"]
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmarks")
    os.makedirs(out_dir, exist_ok=True)
    program, controls = [], []
    for i, seed in enumerate(args.seeds):
        rows = traffic_lib.make(
            traffic, vocab_size=family.vocab_size, eod_id=family.eod_id,
            global_batch=traffic["seqs_per_chip"] * cell["chips"],
            seq_len=traffic["seq_len"], seed=seed).check_rows(n_check)
        got = {"program": correct.at_the_seed(family, mesh, seed, rows,
                                              limit)}
        program.append(got["program"])
        if i < args.controls:
            got["control"] = correct.at_the_seed(control(family), mesh, seed,
                                                 rows, limit)
            controls.append(got["control"])
        with open(os.path.join(
                out_dir, f"control.{args.cell}.seed{seed}.json"), "w") as f:
            json.dump(got, f, indent=1)
        print(json.dumps({"cell": args.cell, "seed": seed, **{
            who: {"ok": r["ok"], **{k: r[k] for k in NUMBERS}}
            for who, r in got.items()}}), flush=True)
    print(json.dumps({
        "cell": args.cell, "device": jax.local_devices()[0].device_kind,
        "program_largest": {k: max(g[k] for g in program) for k in NUMBERS},
        "control_smallest": {k: min(g[k] for g in controls)
                             for k in NUMBERS if controls},
        "seed_grad_tol": limit}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
