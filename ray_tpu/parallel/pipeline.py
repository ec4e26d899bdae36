"""Pipeline parallelism: GPipe microbatch schedule over the `pipe` mesh axis.

The reference has NO native pipeline parallelism (SURVEY §2.3 — PP arises only
inside integrated frameworks, or via Compiled Graph channels driven by external
engines like vLLM).  Here it is native and TPU-shaped: the whole pipeline is
ONE jitted SPMD program.  `jax.shard_map` is entered manually over only the
`pipe` axis (partial-manual; every other mesh axis stays auto so XLA keeps
sharding dp/fsdp/tensor/seq inside each stage), stage handoffs are
`lax.ppermute` point-to-point transfers that ride a single ICI/DCN hop, and
the microbatch loop is a `lax.scan`, so the schedule is reverse-mode
differentiable and the backward pipeline is derived by AD (scan + ppermute
transpose) rather than hand-scheduled.

Schedule: classic GPipe.  With S stages and M microbatches the loop runs
S+M-1 ticks; at tick t stage s computes microbatch t-s (bubble fraction
(S-1)/(S+M-1) — pick M >= 4*S to amortize).  All stages execute every tick
(SPMD), so the bubble costs FLOPs, not correctness.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

# jax imports are function-local, matching mesh.py: importing this package
# must not initialize jax (tests/conftest.py sets platform env first).

PIPE_AXIS = "pipe"


def _pipeline_local(stage_fn: Callable[[Any, Any], Any],
                    stage_params: Any,
                    x_mb,
                    *,
                    axis_name: str,
                    n_microbatches: int):
    """shard_map body. `stage_params` leaves carry this stage's leading-axis
    slice (layers-per-stage first dim); `x_mb` is (M, mb, ...) replicated
    over the pipe axis."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_stages = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    ticks = n_microbatches + n_stages - 1
    # Shift chain toward the next stage; the final stage's output is dropped
    # from the permute ring (open chain, not a ring — no wraparound hazard).
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    state = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
    out_buf = jnp.zeros_like(x_mb)
    if hasattr(lax, "pcast"):
        # Carry values mix in ppermuted data, so they are device-varying over
        # `pipe`; mark the zero inits to satisfy shard_map's vma check.
        state = lax.pcast(state, (axis_name,), to="varying")
        out_buf = lax.pcast(out_buf, (axis_name,), to="varying")

    def tick(carry, t):
        state, out_buf = carry
        mb = jnp.clip(t, 0, n_microbatches - 1)
        inp = jnp.where(stage == 0, x_mb[mb], state)
        out = stage_fn(stage_params, inp)
        oi = jnp.clip(t - (n_stages - 1), 0, n_microbatches - 1)
        write = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
        out_buf = jnp.where(
            write, lax.dynamic_update_index_in_dim(out_buf, out, oi, 0), out_buf)
        state = lax.ppermute(out, axis_name, perm)
        return (state, out_buf), None

    (state, out_buf), _ = lax.scan(tick, (state, out_buf), jnp.arange(ticks))
    # Only the last stage holds real outputs; psum over the open chain
    # replicates them to every stage (zeros elsewhere; the sum is exact in
    # any dtype since exactly one term is nonzero).  On CPU the carry is
    # already fp32 (see pipeline_apply's carry_fp32 workaround).
    out_buf = jnp.where(stage == n_stages - 1, out_buf, jnp.zeros_like(out_buf))
    return lax.psum(out_buf, axis_name)


def pipeline_apply(stage_fn: Callable[[Any, Any], Any],
                   stage_params: Any,
                   x,
                   *,
                   n_microbatches: int,
                   axis_name: str = PIPE_AXIS,
                   mesh=None):
    """Run `x` through a pipeline of identical stages over the `pipe` axis.

    Args:
      stage_fn: (local_params, activations) -> activations.  Receives the
        LOCAL leading-axis slice of `stage_params` (shape
        (layers_per_stage, ...) per leaf) — typically it `lax.scan`s its
        layers.  Must preserve the activation shape (pipelines are
        shape-homogeneous by construction).
      stage_params: pytree whose leaves have a leading stacked-layer axis
        divisible by the pipe axis size; sharded leading-dim over `pipe`
        (logical axis name "layers", mesh.DEFAULT_RULES).
      x: (B, ...) activations; B % n_microbatches == 0.
      n_microbatches: GPipe microbatch count M (bubble = (S-1)/(S+M-1)).
      mesh: optional; defaults to the ambient mesh (jax.set_mesh).

    Returns activations of x's shape, replicated over `pipe` (sharding over
    all other mesh axes is untouched — they stay auto).
    """
    import jax
    import jax.numpy as jnp

    P = jax.sharding.PartitionSpec
    B = x.shape[0]
    if B % n_microbatches != 0:
        raise ValueError(f"batch {B} % n_microbatches {n_microbatches} != 0")

    # Validate the layer stack against the ACTUAL pipe axis size (the mesh is
    # authoritative — a config's stage count can silently disagree with it).
    resolved = mesh if mesh is not None else jax.sharding.get_abstract_mesh()
    if axis_name in resolved.shape:
        n_stages = resolved.shape[axis_name]
        for leaf in jax.tree.leaves(stage_params):
            if leaf.shape[0] % n_stages:
                raise ValueError(
                    f"stage_params leading dim {leaf.shape[0]} not divisible "
                    f"by pipe axis size {n_stages}")

    # XLA CPU (the 8-virtual-device test platform) miscompiles the bf16
    # psum_invariant all-reduce that AD emits for the replicated microbatch
    # input (checkfail in AllReducePromotion).  Carry activations in fp32
    # there; on TPU the carry stays in the compute dtype.
    compute_dtype = x.dtype
    carry_fp32 = (jax.default_backend() == "cpu"
                  and compute_dtype == jnp.bfloat16)
    if carry_fp32:
        x = x.astype(jnp.float32)
        inner_fn, stage_fn = stage_fn, lambda p, h: inner_fn(
            p, h.astype(compute_dtype)).astype(jnp.float32)
    x_mb = x.reshape(n_microbatches, B // n_microbatches, *x.shape[1:])

    params_spec = jax.tree.map(lambda _: P(axis_name), stage_params)
    fn = partial(_pipeline_local, stage_fn, axis_name=axis_name,
                 n_microbatches=n_microbatches)
    out = jax.shard_map(fn, mesh=mesh,
                        in_specs=(params_spec, P()),
                        out_specs=P(),
                        axis_names={axis_name})(stage_params, x_mb)
    return out.reshape(B, *x.shape[1:]).astype(compute_dtype)
