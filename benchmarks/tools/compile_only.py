"""Compile a cell's programs at their real size for a described v5e host,
without a chip (``on-chip-measurement`` guide, section 2.3):

    JAX_PLATFORMS=cpu python benchmarks/tools/compile_only.py [cell ...]

For each cell: the train step, and the four programs of the reference check
(the program's loss and the plain reference's at the cell's S, each with its
gradients at the shortened S), lowered for ``v5e:2x2`` devices under the
cell's layout.  Prints per-device ``memory_analysis()``, the Mosaic calls and
the collectives.  A compile that passes is not a chip run: nothing here is a
time.  ``--hlo DIR`` also writes each step's compiled text.

The program asks ``jax.default_backend()`` and ``jax.devices()`` to choose its
kernels; here both still see the CPU, so this script (and nothing in the
program) points them at the described chip while it lowers.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def gib(n: int) -> str:
    return f"{n / 2 ** 30:.2f}"


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("cells", nargs="*")
    parser.add_argument("--hlo")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from benchmarks.lib import correct, spec, state
    from benchmarks.lib.compile_watch import hlo_report
    from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh
    from ray_tpu.parallel.mesh import pytree_sharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"
    jax.devices = lambda *a: list(topo.devices)

    bench = spec.load_benchmark()
    names = args.cells or [w["name"] for w in bench["workloads"]]
    print("| program | params | arguments GiB | output GiB | temp GiB | "
          "Mosaic calls | all-gather / all-reduce / reduce-scatter | "
          "compile s |")
    print("|---|---|---|---|---|---|---|---|")
    for name in names:
        cell = spec.load_cell(bench, name)
        config, traffic = cell["config_file"], cell["traffic_file"]
        S, chips = traffic["seq_len"], cell["chips"]
        B = traffic["seqs_per_chip"] * chips
        family = spec.load_module("models", config["family"]).build(config, S)
        mesh = make_mesh(MeshSpec(**config["layout"]["mesh"]),
                         topo.devices[:chips])
        shardings = pytree_sharding(family.logical_axes, mesh)
        params = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            jax.eval_shape(family.init_fn, jax.random.key(0)), shardings)
        n_params = sum(a.size for a in jax.tree.leaves(params))
        # the optimizer the train loop hands the program (kinds/train.py)
        optimizer = state.born_sharded(family.make_optimizer(), shardings)

        def row(label, jitted, *abstract):
            t0 = time.perf_counter()
            with jax.set_mesh(mesh):
                compiled = jitted.lower(*abstract).compile()
            seconds = time.perf_counter() - t0
            mem, hlo = compiled.memory_analysis(), compiled.as_text()
            rep = hlo_report(hlo)
            c = rep["collectives"]
            print(f"| {name} {label} | {n_params / 1e6:.1f} M | "
                  f"{gib(mem.argument_size_in_bytes)} | "
                  f"{gib(mem.output_size_in_bytes)} | "
                  f"{gib(mem.temp_size_in_bytes)} | {len(rep['mosaic'])} | "
                  f"{c['all-gather']} / {c['all-reduce']} / "
                  f"{c['reduce-scatter']} | {seconds:.0f} |", flush=True)
            return compiled, hlo

        # create_sharded_state's own call, so the state lies here as the
        # program leaves it, not as this tool would like it
        with jax.set_mesh(mesh):
            init = jax.jit(optimizer.init).lower(params).compile()
        opt_state = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            jax.eval_shape(optimizer.init, params), init.output_shardings)
        batch = jax.ShapeDtypeStruct((B, S), jnp.int32,
                                     sharding=batch_sharding(mesh))
        _, hlo = row(f"step {B}x{S}",
                     jax.jit(family.make_train_step(optimizer),
                             donate_argnums=(0, 1)),
                     params, opt_state, batch, batch)
        if args.hlo:
            os.makedirs(args.hlo, exist_ok=True)
            with open(os.path.join(args.hlo, name + ".step.hlo.txt"),
                      "w") as f:
                f.write(hlo)

        n = mesh.shape["data"] * mesh.shape["fsdp"]
        Sg = min(S, correct.GRAD_SEQ)
        full = jax.ShapeDtypeStruct((n, S), jnp.int32,
                                    sharding=batch_sharding(mesh))
        cut = jax.ShapeDtypeStruct((n, Sg), jnp.int32,
                                   sharding=batch_sharding(mesh))
        row(f"check: program loss {n}x{S}", jax.jit(family.loss_fn), params,
            full, full)
        row(f"check: reference loss {n}x{S}", jax.jit(
            lambda p, t, y: family.reference_loss(p, t, y, correct.Q_BLOCK)),
            params, full, full)
        row(f"check: program grads {n}x{Sg}",
            correct.value_and_grad(family.loss_fn, params), params, cut, cut)
        row(f"check: reference grads {n}x{Sg}", correct.value_and_grad(
            lambda p, t, y: family.reference_loss(p, t, y, Sg), params),
            params, cut, cut)
    return 0


if __name__ == "__main__":
    sys.exit(main())
