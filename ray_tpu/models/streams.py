"""The residual of several streams under manifold-constrained
hyper-connections (mHC, arXiv:2512.24880, over Hyper-Connections,
arXiv:2409.19606): the residual path of a hybrid decoder
(``models/hybrid.py``) whose configuration states ``streams`` > 1.  Not a
kind and not a model: the file is the stream maps of one sub-layer, the read
that hands the sub-layer's branch its input, the write that takes its output
back, their parameters (one more stack, ``hc``, a row a sub-layer) and their
sizes.

``n`` = ``streams``, ``C`` = ``d_model``; ``X`` is (n, C) a position.  Around
the branch ``f`` of every sub-layer (a kind's ``branch``: its own pre-norm,
no residual add):

    x~      = vec(X) / sqrt(mean(vec(X)^2) + rms_eps)      1 x nC, no weight
    H~_pre  = alpha_pre  (x~ phi_pre)  + b_pre              1 x n
    H~_post = alpha_post (x~ phi_post) + b_post             1 x n
    H~_res  = alpha_res  mat(x~ phi_res) + b_res            n x n, row-major
    H_pre   = sigmoid(H~_pre),   H_post = 2 sigmoid(H~_post)
    M_0     = exp(clip(H~_res, hc_clamp))
    M_t     = T_r(T_c(M_{t-1})),  t = 1 .. hc_sinkhorn_iters
              T_c: each column / (its sum + hc_eps), then T_r: each row
    H_res   = M_last                                        doubly stochastic
    u       = H_pre X                                       what ``f`` reads
    X'      = H_res X + H_post^T f(u)

``phi`` is (nC, n^2 + 2n): the columns of ``phi_pre``, ``phi_post`` and
``phi_res`` side by side, the rows stream-major as ``vec`` reads ``X``;
``alpha`` the three scalars; ``base`` the n + n + n^2 biases.

**Layout.**  The streams are a tuple of n arrays (B, S, C) in the compute
dtype, each laid out as the one-stream model's ``x`` is: an axis of 4
beside the lanes would be padded to a tile's 16 rows, and a leading axis
made the compiler transpose the streams to suit the maps (40 GB a step of
copies in the first compile of the cell, PERF.md PR 62).  The product with
``phi`` is a sum of one product a stream, (B, S, C) x (C, n^2 + 2n), in the
compute dtype with float32 accumulation as every weight's product is; the
division by the norm follows it (a scalar a position), and from there to
``H_res`` everything is float32.  The turns run with the positions last, (n,
n, B x S) folded into lane tiles: element-wise over whole rows of positions
and not 4 x 4 matrices a position.  What leaves is one array ``H``: (B, S,
n^2 + 2n), a position's numbers side by side (``H_pre | H_post | H_res`` row-major), so that the
read and the write take a position's weight from a lane and spread it over
the position's lanes: multiply-adds over the streams in float32 that round
once.  The turns are a loop of a fixed count, differentiated as written
(:func:`sinkhorn`).

**What a layer keeps.**  ``H`` bears the name ``remat.MAPS`` (24 float32 a
position a sub-layer at n = 4): where ``ops/remat.py`` finds room for it
the backward's second forward skips the norm, the product and the turns.

**The seams.**  What crosses between the maps, the read or the write and
their neighbours (the streams coming in and going out, ``H``, ``u``, ``y``)
crosses an
``optimization_barrier``: the compiler fuses nothing across, so the three
scopes below hold their own work and no neighbour's (without them the read
rode in the branch's norm and the write in the next sub-layer's sums, and
the trace read a twelfth of the mix's time; PERF.md, PR 62), and a kernel
that takes a scope's place meets the same seams.

**Two paths.**  Where ``ops/streams_kernel.py:path`` says so (on the chip,
four streams, no mesh or one device, ``C`` whole lane tiles, the tokens whole
blocks) a sub-layer's maps with its read, its write and their backwards are
that module's four Mosaic passes over whole rows of the streams, between the
same seams; the expressions below are the other path (the CPU, a mesh, the
tests' oracle) and say what the kernels compute.

Scopes: ``mhc`` holds all of it (and the counter's own sums), inside it
``mhc_maps`` (norm, product, sigmoids, turns) and ``mhc_mix`` (the read and
the write); the branch runs between the two under its kind's own scopes.
The step counter
``mhc_sinkhorn_err``: the largest distance from 1 of a row or column sum of
``H_res`` over a sub-layer's positions.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import remat, streams_kernel
from ray_tpu.util.tracing import step_counter

#: folded into ``hybrid.init_params``' key for the ``hc`` stack
DRAW = 62
#: the name of the stack in the parameter tree
STACK = "hc"


def maps_width(config) -> int:
    """The maps' numbers a position: n read weights, n write weights, n^2
    of the stream map."""
    n = config.streams
    return n * n + 2 * n


def init_params(config, key, rows: int) -> Dict[str, Any]:
    """``rows`` sub-layers' maps stacked on a leading axis: ``phi``
    normal(0.02), ``alpha`` 0.01 each, ``base`` a read that is the streams'
    mean (``-ln(n - 1)`` under the sigmoid), a write of weight 1 to every
    stream (0 under twice the sigmoid) and a stream map that is nearly the
    identity (0 on the diagonal, -8 off it, before the turns)."""
    n = config.streams
    base = np.concatenate([
        np.full(n, -math.log(n - 1)), np.zeros(n),
        np.where(np.eye(n, dtype=bool), 0.0, -8.0).ravel()])
    return {"phi": jax.random.normal(
                key, (rows, n * config.d_model, maps_width(config)),
                jnp.float32) * 0.02,
            "alpha": jnp.full((rows, 3), 0.01, jnp.float32),
            "base": jnp.tile(jnp.asarray(base, jnp.float32), (rows, 1))}


def logical_axes(config) -> Dict[str, Any]:
    """Of the stacked leaves: whole on every chip (the maps are data-parallel
    as the norms are; ROADMAP B: streams under a `tensor` or `seq` axis)."""
    return {"phi": ("layers", None, None), "alpha": ("layers", None),
            "base": ("layers", None)}


def matmul_params(config) -> int:
    """The matrix entries of one sub-layer's maps that a position meets."""
    return config.streams * config.d_model * maps_width(config)


def num_params(config) -> int:
    """Of one sub-layer's maps."""
    return matmul_params(config) + 3 + maps_width(config)


def layer_bytes(config, tokens: int, itemsize: int):
    """For ``hybrid._layer_sizes``, a chip's bytes of one sub-layer's maps,
    read and write over ``tokens`` positions: (their working set: the
    streams' cotangent coming in, the one going out and a pass of one of
    them between, in the compute dtype; what is kept beside the layer's
    input: nothing; the rung it names: the maps, which spare the norm's pass
    over every stream, the product that makes their logits and the Sinkhorn
    turns' passes over the n x n of them).  Where the kernels run
    (``ops/streams_kernel.py``) the working set is the write's backward's
    (the cotangent coming in, ``y``'s and the share going out), and the maps
    kept spare nothing: the second forward's one pass over the streams makes
    them on the way to ``u``, which it reads the streams for anyway."""
    n, D = config.streams, config.d_model
    wide = tokens * n * D
    if streams_kernel.path(tokens, D, n, itemsize,
                           jax.sharding.get_abstract_mesh()) == "kernel":
        return ((2 * wide + tokens * D) * itemsize, 0,
                {remat.MAPS: (tokens * maps_width(config) * 4,
                              remat.spared())})
    return (wide * 3 * itemsize, 0,
            {remat.MAPS: (tokens * maps_width(config) * 4, remat.spared(
                flops=2.0 * wide * maps_width(config),
                moved=wide * itemsize + 2 * config.hc_sinkhorn_iters
                * tokens * n * n * 4))})


def first_call_facts(config, sublayers: int) -> Dict[str, Any]:
    return {"streams": config.streams,
            "hc_sinkhorn_iters": config.hc_sinkhorn_iters,
            "mhc_sublayers": sublayers}


def _sum(terms):
    return functools.reduce(operator.add, terms)


#: positions a row of the turns' arrays: the chip's lanes
_LANES = 128


def sinkhorn(logits, iters: int, eps: float, clamp: Tuple[float, float]):
    """(n, n, T) logits, the map's rows and then its columns in front, T
    positions -> the matrix after ``iters`` turns of a column and then a
    row normalisation of ``exp(clip(logits))``.

    The positions are folded into whole lane tiles, (n, n, T / 128, 128),
    so that both sums run over leading axes: element-wise adds of whole
    tiles.  The turns are a ``lax.scan`` over a fixed count
    (``hc_sinkhorn_iters`` is a count: no early exit, nothing
    data-dependent), each turn under ``jax.checkpoint``: the gradient is
    the turns' own reverse pass as written, every turn made again from its
    input, no implicit gradient.  Timed alone on the chip, twelve
    sub-layers' turns forward and backward at 8,192 positions (PERF.md, PR
    62): 0.73 ms and 1.6 s of compile in this form; unrolled over (n, n, T)
    2.30 ms and 33 s, unrolled over the folded positions 1.47 ms and 47 s."""
    n, _, T = logits.shape
    lanes = _LANES if T % _LANES == 0 else 1

    @jax.checkpoint
    def turn(m, _):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=1, keepdims=True) + eps), None

    start = jnp.exp(jnp.clip(logits, *clamp)).reshape(n, n, T // lanes, lanes)
    return lax.scan(turn, start, None, length=iters)[0].reshape(n, n, T)


def sinkhorn_err(res):
    """The largest distance from 1 of a row or column sum of ``res``, (n, n,
    T), over its positions: a float32 scalar without a gradient."""
    res = lax.stop_gradient(res)
    return jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0)),
                       jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0)))


def maps(X, hc, config):
    """One sub-layer's maps over the streams ``X``, n arrays (B, S, C);
    ``hc`` its row of the stack -> (``H``: (B, S, n^2 + 2n) float32, a
    position's ``H_pre | H_post | H_res`` side by side, named
    ``remat.MAPS``; ``H_res`` as the turns leave it, (n, n, B x S))."""
    n, (B, S, C) = len(X), X[0].shape
    with jax.named_scope("mhc_maps"):
        phi = hc["phi"].reshape(n, C, -1).astype(X[0].dtype)
        squares = _sum(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1)
                       for x in X)
        raw = _sum(jnp.matmul(x, phi[j], preferred_element_type=jnp.float32)
                   for j, x in enumerate(X))
        alpha = jnp.repeat(hc["alpha"], np.array([n, n, n * n]),
                           total_repeat_length=n * n + 2 * n)
        logits = raw * lax.rsqrt(squares / (n * C) + config.rms_eps)[
            ..., None] * alpha + hc["base"]
        # the positions last for the turns, and back
        logits = logits.reshape(B * S, -1).T
        pre = jax.nn.sigmoid(logits[:n])
        post = 2.0 * jax.nn.sigmoid(logits[n:2 * n])
        res = sinkhorn(logits[2 * n:].reshape(n, n, B * S),
                       config.hc_sinkhorn_iters, config.hc_eps,
                       config.hc_clamp)
        H = jnp.concatenate([pre, post, res.reshape(n * n, B * S)]).T
        return checkpoint_name(H.reshape(B, S, -1), remat.MAPS), res


def read(X, H):
    """``H_pre X``: (B, S, C) in the streams' dtype."""
    with jax.named_scope("mhc_mix"):
        return sum(H[..., j:j + 1] * x.astype(jnp.float32)
                   for j, x in enumerate(X)).astype(X[0].dtype)


def write(X, y, H):
    """``H_res X + H_post^T y``: the n streams in their dtype."""
    n = len(X)
    with jax.named_scope("mhc_mix"):
        X32 = [x.astype(jnp.float32) for x in X]
        y32 = y.astype(jnp.float32)
        return tuple(
            (sum(H[..., 2 * n + i * n + j:2 * n + i * n + j + 1] * X32[j]
                 for j in range(n))
             + H[..., n + i:n + i + 1] * y32).astype(X[0].dtype)
            for i in range(n))


def layer(config, branch):
    """A sub-layer under the maps, as (the streams, the kind's row of its
    stack, the sub-layer's row of ``hc``) -> (the streams, the step counters
    it leaves: the branch's and ``mhc_sinkhorn_err``).  ``branch``: the
    kind's, (u, its row) -> (f(u), its counters or None)."""
    def hyper(X, blk, hc):
        n = len(X)
        kernel = streams_kernel.engaged(X)
        with jax.named_scope("mhc"):
            # one barrier for the three readers, so that the sum of their
            # cotangents is traced where they are and not out here
            X = lax.optimization_barrier(X)
            if kernel:  # the maps with the read; the write reads its X
                H, u, X = streams_kernel.maps_read(X, hc, config)
                H = checkpoint_name(H, remat.MAPS)
                res = jnp.moveaxis(H[..., 2 * n:].reshape(-1, n, n), 0, -1)
            else:
                H, res = maps(X, hc, config)
            err = sinkhorn_err(res)
            H = lax.optimization_barrier(H)
            u = lax.optimization_barrier(u if kernel else read(X, H))
        y, counted = branch(u, blk)
        with jax.named_scope("mhc"):
            X = lax.optimization_barrier(
                (streams_kernel.write if kernel else write)(
                    X, lax.optimization_barrier(y), H))
        return X, {**(counted or {}), step_counter("mhc_sinkhorn_err"): err}

    return hyper
