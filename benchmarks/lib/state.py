"""The optimizer a multi-chip cell hands the program.

``create_sharded_state`` builds the optimizer state with
``jax.jit(optimizer.init)(params)``.  optax makes the moments from zeros,
which carry no sharding to propagate, so on a mesh they come out replicated:
at 2885.8 M parameters 17.3 GB of moments on every chip
(``RESOURCE_EXHAUSTED``, PERF.md, PR 22).  The repair belongs to the program
(ROADMAP A1) and no benchmark PR may make it there, so the train loop, which
is the user's code, hands the program an optimizer whose ``init`` says where
its moments lie.  ``update`` is the optimizer's own, so the step is the
program's.  Once the program pins the state itself the constraint says what
is already so.
"""

from __future__ import annotations


def _shaped_like(tree):
    import jax

    treedef = jax.tree.structure(tree)
    return lambda node: jax.tree.structure(node) == treedef


def mirrors(opt_state, params):
    """The sub-trees of an optimizer state shaped like the parameters
    (AdamW's ``mu`` and ``nu``), in order."""
    import jax

    mirror = _shaped_like(params)
    return [node for node in jax.tree.leaves(opt_state, is_leaf=mirror)
            if mirror(node)]


def born_sharded(optimizer, shardings):
    """``optimizer`` with every mirror of its fresh state constrained to
    ``shardings``, the parameters' own, leaf for leaf; every other leaf (the
    counts) is left to the compiler.  On one device there is nothing to say
    and the optimizer comes back as it is."""
    import jax
    import optax

    if jax.tree.leaves(shardings)[0].mesh.size == 1:
        return optimizer
    mirror = _shaped_like(shardings)

    def init(params):
        return jax.tree.map(
            lambda node: jax.lax.with_sharding_constraint(node, shardings)
            if mirror(node) else node,
            optimizer.init(params), is_leaf=mirror)

    return optax.GradientTransformation(init, optimizer.update)
