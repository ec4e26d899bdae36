"""The comparison that decides ``correct``: the program's own loss and
gradients (its kernels, its mesh, the trained parameters, the published
widths) against the family's plain reference, outside the timed window.

Errors are ``max|system - reference| / max|reference|``, per leaf.

Tolerances, from 45 runs of the three cells on the chip (PR 22, PERF.md).
The program multiplies in bfloat16 (rounding error 2^-9 per operand),
accumulates in float32, keeps the residual stream in bfloat16 and materialises
its logits in bfloat16; the reference is float32 throughout.

``LOSS_TOL`` is the guard on precision.  A mean cross-entropy averages
thousands of positions whose rounding errors are independent: it differed
from the reference by 0.4e-5 to 2.8e-4 of its value, and the tolerance is 3.5
times the largest seen.  An 8-bit float (rounding error 2^-4, 32 times
bfloat16's) or a bfloat16 accumulator over K=4096 would put it at several
1e-3 and fail.

``GRAD_TOL`` is the guard on the mathematics, not on precision.  After some
tens of steps the bfloat16 logits alone perturb every position's gradient by a
few percent, and one row's gradient is a sum of contributions that largely
cancel, so a leaf's largest error against its largest value was 0.01 to 0.12
(``mistral7b-s8192``), 0.03 to 0.25 (``mistral7b-s1024``) and 0.05 to 0.33
(``gpt2xl-s1024``, LayerNorm parameters and ``wte`` worst); norm-wise errors
(recorded beside them as ``grad_norm_err_by_leaf``) are no steadier.  The
tolerance is a little over twice the largest seen: it fails an error of the
size of the leaf itself (a lost term, a wrong mask, a doubled gradient) and
passes bfloat16.  A tighter gradient check needs a quieter comparison
(PERF.md, Open questions).

**A configuration may state its own** (its file's ``check``; PR 30, readings
in PERF.md section 2).  The loss error grows with depth and with how sharp a
model the window left: twelve layers read 1.6e-4 to 4.6e-4 where two read
1e-6 to 1.6e-4, and a run that ended inside a loss spike 4.7e-3, so on such a
configuration ``loss_tol`` guards the mathematics on the trained parameters
and no longer the precision.  That is then the work of ``seed_grad_tol``: a
second comparison of the gradients, on the parameters the seed gives
(:func:`at_the_seed`), where a leaf's error ``|g - g_ref|_2 / |g_ref|_2``
depends on the seed by a few percent; its limit is on the median over the
leaves, and no leaf may read over ``LEAF_FACTOR`` times it.  A configuration
that states nothing is compared as it always was.
"""

from __future__ import annotations

import statistics
from typing import Dict, Optional

from benchmarks.lib import state

LOSS_TOL = 1e-3
GRAD_TOL = 0.75
#: Without a kernel the backward keeps the S x S probabilities (8.6 GB a
#: layer at S=8192), so gradients are compared on a sequence cut to this.
GRAD_SEQ = 1024
#: Query block of the reference's forward-only attention at the cell's S.
Q_BLOCK = 512
#: at the seed no leaf may read more than this many times ``seed_grad_tol``
LEAF_FACTOR = 3


def value_and_grad(loss_fn, params):
    """``jax.value_and_grad(loss_fn)`` jitted, with the gradients cut as the
    parameters are.  Left to itself the compiler hands the program's
    gradients back whole on every chip (10.75 GiB at 2885.8 M parameters on
    four chips, compile-only, PR 30): nothing after them says otherwise."""
    import jax

    return jax.jit(jax.value_and_grad(loss_fn), out_shardings=(
        None, jax.tree.map(lambda p: p.sharding, params)))


def _rel_err(a, b):
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))


def _norm_err(a, b):
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2))


def gradients(family, params, tokens, targets, mesh) -> Dict:
    """Loss and every gradient leaf, program against reference, on the
    sequence cut to ``GRAD_SEQ``."""
    import jax

    S = min(int(tokens.shape[1]), GRAD_SEQ)
    tokens, targets = tokens[:, :S], targets[:, :S]
    with jax.set_mesh(mesh):
        sys_loss, sys_grads = value_and_grad(family.loss_fn, params)(
            params, tokens, targets)
    ref_loss, ref_grads = value_and_grad(
        lambda p, t, y: family.reference_loss(p, t, y, S), params)(
        params, tokens, targets)

    def by_leaf(fn):
        errs = jax.jit(lambda a, b: jax.tree.map(fn, a, b))(
            sys_grads, ref_grads)
        return {jax.tree_util.keystr(path): float(err) for path, err
                in jax.tree_util.tree_flatten_with_path(errs)[0]}

    leaves, norms = by_leaf(_rel_err), by_leaf(_norm_err)
    return {"grad_seq_len": S, "grad_system_loss": float(sys_loss),
            "grad_reference_loss": float(ref_loss),
            "grad_loss_err": float(_rel_err(sys_loss, ref_loss)),
            "grad_err_by_leaf": leaves, "grad_err_max": max(leaves.values()),
            "grad_norm_err_by_leaf": norms,
            "grad_norm_err_max": max(norms.values()),
            "grad_norm_err_median": statistics.median(norms.values())}


def compare(family, params, tokens, targets, mesh,
            loss_tol: Optional[float] = None) -> Dict:
    """``tokens``/``targets``: one sequence per batch shard, at the cell's S,
    already placed with the mesh's batch sharding.  ``loss_tol``: the
    configuration's own, else ``LOSS_TOL``."""
    import jax

    # Forward only, at the cell's own sequence length.
    with jax.set_mesh(mesh):
        system = jax.jit(family.loss_fn)(params, tokens, targets)
    ref = jax.jit(lambda p, t, y: family.reference_loss(p, t, y, Q_BLOCK))(
        params, tokens, targets)
    out = {"seq_len": int(tokens.shape[1]), "system_loss": float(system),
           "reference_loss": float(ref),
           "loss_err": float(_rel_err(system, ref))}
    out.update(gradients(family, params, tokens, targets, mesh))
    out.update(loss_tol=loss_tol or LOSS_TOL, grad_tol=GRAD_TOL)
    out["ok"] = bool(out["loss_err"] <= out["loss_tol"]
                     and out["grad_loss_err"] <= out["loss_tol"]
                     and out["grad_err_max"] <= GRAD_TOL)
    return out


def at_the_seed(family, mesh, seed: int, rows, seed_grad_tol: float) -> Dict:
    """:func:`gradients` on the parameters the program's ``init_fn`` draws
    from ``seed``, made anew on the mesh, and on ``rows`` (n x S+1 ids, one
    row a batch shard): numbers that depend on the seed and on nothing a run
    did."""
    import jax

    from ray_tpu.parallel import batch_sharding
    from ray_tpu.parallel.train_state import create_sharded_state

    params, _ = create_sharded_state(family.init_fn, family.logical_axes,
                                     mesh, jax.random.key(seed))
    tokens, targets = (jax.device_put(a, batch_sharding(mesh))
                       for a in (rows[:, :-1], rows[:, 1:]))
    out = gradients(family, params, tokens, targets, mesh)
    out.update(seed_grad_tol=seed_grad_tol,
               leaf_tol=LEAF_FACTOR * seed_grad_tol)
    out["ok"] = bool(out["grad_norm_err_median"] <= seed_grad_tol
                     and out["grad_norm_err_max"] <= out["leaf_tol"])
    return out


def placement(params, opt_state, batch, expected, seqs_per_chip: int,
              n_chips: int) -> Dict:
    """Do the state's shards lie where the configuration's layout says?
    Every parameter, and every optimizer leaf that mirrors one, must lie on
    all ``n_chips`` devices cut exactly as ``expected`` (the layout's
    NamedSharding pytree) cuts it, and each chip must hold ``seqs_per_chip``
    rows of the batch.  A leaf cut finer than the layout says (the compiled
    step shards small vectors the layout replicates) costs no memory and is
    listed under ``finer``; a leaf held in larger pieces is ``wrong``."""
    import jax
    import numpy as np

    wrong, finer = [], []

    def check(tree, label):
        for (path, leaf), want in zip(
                jax.tree_util.tree_flatten_with_path(tree)[0],
                jax.tree.leaves(expected)):
            name = label + jax.tree_util.keystr(path)
            shapes = {s.data.shape for s in leaf.addressable_shards}
            devices = {s.device for s in leaf.addressable_shards}
            exact = (leaf.sharding.devices_indices_map(leaf.shape)
                     == want.devices_indices_map(leaf.shape))
            if len(devices) != n_chips or len(shapes) != 1:
                wrong.append(f"{name}: on {len(devices)} devices, shards "
                             f"{sorted(shapes)}")
            elif not exact:
                held = int(np.prod(next(iter(shapes))))
                if held < int(np.prod(want.shard_shape(leaf.shape))):
                    finer.append(f"{name}: {leaf.sharding.spec}")
                else:
                    wrong.append(f"{name}: {leaf.sharding.spec}, layout "
                                 f"says {want.spec}")

    check(params, "params")
    moments = state.mirrors(opt_state, params)
    for i, tree in enumerate(moments):
        check(tree, f"opt_state[{i}]")
    rows = sorted((s.device.id, s.data.shape[0])
                  for s in batch.addressable_shards)
    if [r for _, r in rows] != [seqs_per_chip] * n_chips:
        wrong.append(f"batch rows per chip {rows}, want {seqs_per_chip} on "
                     f"each of {n_chips}")
    return {"ok": not wrong, "wrong": wrong[:8], "finer": finer,
            "optimizer_mirrors": len(moments), "batch_rows": rows}
