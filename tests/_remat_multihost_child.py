"""Child process for ``tests/test_remat.py``'s two-process test: joins a
2-process jax.distributed cluster in which each process's chips report
other memory (rank 0 the fuller: room for the first rung where rank 1 has
room for both), and runs one Llama train step over the global ``fsdp`` mesh.

Run with env: COORD, NPROC, RANK, CHILD_DEVICES.  Prints one line:
  RESULT <rank> <kept, comma separated, or -> <processes> <alone> <loss>
where ``alone`` is what this process would have kept from its own chips.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices",
                  int(os.environ.get("CHILD_DEVICES", "2")))
# Cross-process CPU collectives ride gloo (the CPU stand-in for the DCN tier).
jax.config.update("jax_cpu_collectives_implementation", "gloo")

import numpy as np  # noqa: E402

from ray_tpu.collective import distributed as dist  # noqa: E402


def main() -> None:
    dist.initialize(
        coordinator_address=os.environ["COORD"],
        num_processes=int(os.environ["NPROC"]),
        process_id=int(os.environ["RANK"]),
    )
    from ray_tpu.models import llama
    from ray_tpu.ops import remat
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.util import device_telemetry
    from ray_tpu.parallel.train_state import (create_sharded_state,
                                              jit_train_step)

    rank = dist.process_index()
    devices = jax.devices()  # GLOBAL devices across both processes
    mesh = make_mesh(MeshSpec(fsdp=len(devices)), devices)
    config = llama.LlamaConfig.tiny()
    n_local = len(jax.local_devices())
    with jax.set_mesh(mesh):
        sizes = candidates, temporaries = llama._layer_sizes(
            jax.eval_shape(lambda: llama.init_params(config,
                                                     jax.random.key(0))),
            (len(devices), config.seq_len, config.d_model), config)
    # Rank 0 holds more (an evaluation program, a checkpoint's staging): its
    # own chips have room for q/k/v and half of gate and up, rank 1's for
    # everything.
    limit = 1 << 30
    room = candidates[0][1] + candidates[1][1] // 2
    memory = (limit, 0) if rank else (limit, limit - temporaries - room
                                      - int(remat.RESERVE_SHARE * limit))
    remat.device_memory = lambda: memory
    alone = remat.choose(*memory, *sizes)

    optimizer = llama.make_optimizer()
    params, opt_state = create_sharded_state(
        lambda k: llama.init_params(config, k),
        llama.logical_axes(config), mesh, jax.random.key(0), optimizer)
    step = jit_train_step(llama.make_train_step(config, optimizer), mesh=mesh)
    local = np.random.default_rng(rank).integers(
        0, config.vocab_size, (n_local, config.seq_len + 1)).astype(np.int32)
    tokens = dist.local_batch_to_global(mesh, local[:, :-1], axis="fsdp")
    targets = dist.local_batch_to_global(mesh, local[:, 1:], axis="fsdp")
    params, opt_state, loss = step(params, opt_state, tokens, targets)
    (row,) = device_telemetry.first_calls("train_step")
    kept = [name for name, _, _ in row["remat_kept"]]
    print(f"RESULT {rank} {','.join(kept) or '-'} "
          f"{remat.tracing_processes(mesh.abstract_mesh)} "
          f"{','.join(alone.names) or '-'} {float(loss):.6f}", flush=True)
    dist.shutdown()


if __name__ == "__main__":
    main()
