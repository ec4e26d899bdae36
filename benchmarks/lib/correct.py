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
"""

from __future__ import annotations

from typing import Dict

LOSS_TOL = 1e-3
GRAD_TOL = 0.75
#: Without a kernel the backward keeps the S x S probabilities (8.6 GB a
#: layer at S=8192), so gradients are compared on a sequence cut to this.
GRAD_SEQ = 1024
#: Query block of the reference's forward-only attention at the cell's S.
Q_BLOCK = 512


def compare(family, params, tokens, targets, mesh) -> Dict:
    """``tokens``/``targets``: one sequence per batch shard, at the cell's S,
    already placed with the mesh's batch sharding."""
    import jax
    import jax.numpy as jnp

    def rel_err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))

    def reference(q_block):
        return lambda p, t, y: family.reference_loss(p, t, y, q_block)

    # Forward only, at the cell's own sequence length.
    with jax.set_mesh(mesh):
        system = jax.jit(family.loss_fn)(params, tokens, targets)
    ref = jax.jit(reference(Q_BLOCK))(params, tokens, targets)
    loss_err = float(rel_err(system, ref))
    out = {"seq_len": int(tokens.shape[1]), "system_loss": float(system),
           "reference_loss": float(ref), "loss_err": loss_err}

    # Loss and every gradient leaf, on the sequence cut short.
    S = min(int(tokens.shape[1]), GRAD_SEQ)
    tokens, targets = tokens[:, :S], targets[:, :S]
    with jax.set_mesh(mesh):
        sys_loss, sys_grads = jax.jit(jax.value_and_grad(family.loss_fn))(
            params, tokens, targets)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(reference(S)))(
        params, tokens, targets)
    def norm_err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2))

    def by_leaf(fn):
        errs = jax.jit(lambda a, b: jax.tree.map(fn, a, b))(
            sys_grads, ref_grads)
        return {jax.tree_util.keystr(path): float(err) for path, err
                in jax.tree_util.tree_flatten_with_path(errs)[0]}

    leaves = by_leaf(rel_err)
    out["grad_norm_err_by_leaf"] = by_leaf(norm_err)
    out.update({
        "grad_seq_len": S, "grad_system_loss": float(sys_loss),
        "grad_reference_loss": float(ref_loss),
        "grad_loss_err": float(rel_err(sys_loss, ref_loss)),
        "grad_err_by_leaf": leaves, "grad_err_max": max(leaves.values()),
        "loss_tol": LOSS_TOL, "grad_tol": GRAD_TOL})
    out["ok"] = bool(out["loss_err"] <= LOSS_TOL
                     and out["grad_loss_err"] <= LOSS_TOL
                     and out["grad_err_max"] <= GRAD_TOL)
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
    treedef = jax.tree.structure(params)

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

    def mirrors(node):
        """Sub-trees of the optimizer state shaped like the parameters."""
        if jax.tree.structure(node) == treedef:
            yield node
        elif isinstance(node, (tuple, list)):
            for child in node:
                yield from mirrors(child)

    check(params, "params")
    moments = list(mirrors(opt_state))
    for i, tree in enumerate(moments):
        check(tree, f"opt_state[{i}]")
    rows = sorted((s.device.id, s.data.shape[0])
                  for s in batch.addressable_shards)
    if [r for _, r in rows] != [seqs_per_chip] * n_chips:
        wrong.append(f"batch rows per chip {rows}, want {seqs_per_chip} on "
                     f"each of {n_chips}")
    return {"ok": not wrong, "wrong": wrong[:8], "finer": finer,
            "optimizer_mirrors": len(moments), "batch_rows": rows}
