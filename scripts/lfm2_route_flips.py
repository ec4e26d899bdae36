"""What `lfm2-ep4-s8192`'s gradient comparison at the seed is made of (PR 56,
PERF.md section 2): the float32 reference (A) against itself with only the
residual stream rounded to bfloat16 after every sub-layer, routing by its
own scores (B) or handed A's choices (C), on one check row cut to 1024
positions at the parameters ``init_seed`` gives.

    chiprun -- python3 scripts/lfm2_route_flips.py [data seed]

One JSON line: the share of positions whose chosen experts differ between A
and B, a layer (and of them at a held expert), and the median over the
gradient leaves of |g - g_A|_2 / |g_A|_2 for B and for C with every leaf's.
``DIAG_TINY=1`` under ``JAX_PLATFORMS=cpu`` is the rehearsal on
``tiny-lfm2``.  Four minutes on a v5e chip.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from benchmarks.lib import spec, traffic as traffic_lib  # noqa: E402
from benchmarks.lib.cost_lfm2 import layers_run  # noqa: E402
from benchmarks.reference import lfm2_moe as ref  # noqa: E402
from benchmarks.reference.llama import _rmsnorm  # noqa: E402
from ray_tpu.parallel.compile_cache import (  # noqa: E402
    configure_compile_cache)

STACKS = ("shortconv", "attn", "dense", "experts")


def forward(params, tokens, targets, cfg, rounded, forced):
    """-> (the reference's loss, each expert layer's chosen (T, E) mask).
    ``rounded``: the residual stream goes through bfloat16 after every
    sub-layer; ``forced``: masks to route by in the place of its own."""
    eps = cfg["norm_eps"]
    b, S = tokens.shape
    stream = (lambda x: lax.reduce_precision(x, 8, 7)) if rounded \
        else (lambda x: x)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = stream(params["wte"][tokens])
    seen = dict.fromkeys(STACKS, 0)
    masks = []
    first, stop = cfg["experts_held"]

    def row(stack):
        w = jax.tree.map(lambda a: a[seen[stack]], params[stack])
        seen[stack] += 1
        return w

    def held(u, w, weights):
        y = jnp.zeros_like(u)
        for h, e in enumerate(range(first, stop)):
            y = y + weights[:, e, None] * ref.swiglu(
                u, w["w_gate"][h], w["w_up"][h], w["w_down"][h])
        return y

    for layer in layers_run(cfg):
        if cfg["layer_types"][layer] == "conv":
            w = row("shortconv")
            x = stream(jax.checkpoint(lambda x, w: x + ref.short_conv(
                _rmsnorm(x, w["conv_norm"], eps), w))(x, w))
        else:
            w = row("attn")
            x = stream(jax.checkpoint(lambda x, w: x + ref.attention(
                _rmsnorm(x, w["attn_norm"], eps), w, cfg, 1024))(x, w))
        if layer < cfg["num_dense_layers"]:
            w = row("dense")
            x = stream(jax.checkpoint(lambda x, w: x + ref.swiglu(
                _rmsnorm(x, w["mlp_norm"], eps), w["w_gate"], w["w_up"],
                w["w_down"]))(x, w))
            continue
        i = seen["experts"]
        w = row("experts")
        u = _rmsnorm(x, w["mlp_norm"], eps).reshape(b * S, -1)
        scores = jax.nn.sigmoid(u @ w["router"])
        picked = ref.chosen(scores + ref.selection_bias(cfg, i),
                            cfg["num_experts_per_tok"])
        masks.append(picked)
        if forced is not None:
            picked = forced[i]
        weights = jnp.where(picked, scores, 0.0)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        x = stream(x + jax.checkpoint(held)(u, w, weights).reshape(x.shape))
    out = _rmsnorm(x, params["final_norm"], eps) @ params["wte"].T
    lse = jax.nn.logsumexp(out, axis=-1)
    got = jnp.take_along_axis(out, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - got), masks


def errors(grads, against):
    """(the median over the leaves, every leaf's) norm-wise error."""
    by_leaf = jax.tree.map(lambda a, b: float(jnp.sqrt(
        jnp.sum((a - b) ** 2) / jnp.sum(b ** 2))), grads, against)
    flat = {jax.tree_util.keystr(k): round(v, 4) for k, v in
            jax.tree_util.tree_flatten_with_path(by_leaf)[0]}
    return float(np.median(list(flat.values()))), flat


def main() -> int:
    configure_compile_cache()
    cell = spec.load_cell(spec.load_benchmark(), "lfm2-ep4-s8192")
    cfg, traffic = cell["config_file"], cell["traffic_file"]
    tiny = bool(os.environ.get("DIAG_TINY"))
    if tiny:
        cfg = spec.load_json(spec.BENCH_DIR, "configs", "tiny-lfm2.json")
    family = spec.load_module("models", cfg["family"]).build(cfg, 8192)
    params = jax.jit(family.init_fn)(jax.random.key(0))
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 3056000001
    rows = traffic_lib.make(
        traffic, vocab_size=family.vocab_size, eod_id=family.eod_id,
        global_batch=2, seq_len=8192, seed=seed).check_rows(1)
    rows = rows[:, :129 if tiny else 1025]
    tokens, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])

    def run(rounded, forced):
        with jax.default_matmul_precision("highest"):
            (loss, masks), grads = jax.jit(jax.value_and_grad(
                lambda p, f: forward(p, tokens, targets, cfg, rounded, f),
                has_aux=True))(params, forced)
        return float(loss), masks, grads

    loss_a, masks_a, grads_a = run(False, None)
    loss_b, masks_b, grads_b = run(True, None)
    loss_c, _, grads_c = run(True, masks_a)
    first, stop = cfg["experts_held"]
    own, own_leaves = errors(grads_b, grads_a)
    handed, handed_leaves = errors(grads_c, grads_a)
    print(json.dumps({
        "seed": seed, "loss": [loss_a, loss_b, loss_c],
        "tokens_with_a_flip_a_layer": [
            float(jnp.mean(jnp.any(a != b, axis=-1)))
            for a, b in zip(masks_a, masks_b)],
        "of_them_at_a_held_expert": [
            float(jnp.mean(jnp.any(a[:, first:stop] != b[:, first:stop],
                                   axis=-1)))
            for a, b in zip(masks_a, masks_b)],
        "B_own_routing_median": own, "C_forced_routing_median": handed,
        "B": own_leaves, "C": handed_leaves}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
