"""Fused LM-head cross-entropy: logits never materialize in HBM.

The LM head's (B, S, V) logits tensor is the largest intermediate of a
GPT-style train step (1.6 GB for GPT-2-small at B=16 even in bf16, in both
passes).  This kernel computes `mean(logsumexp(x W^T) - x W^T[target])`
with the logits living only in VMEM tiles: the forward streams vocab
blocks through an online logsumexp (same trick flash attention plays over
keys), and the backward recomputes each logits tile to form
`softmax - onehot` on the fly.

Cost model (why this is auto-gated, not the default, for GPT-2-small):
the fully-fused backward recomputes logits twice (once per dx / dW pass),
so the fused step runs 5 head-matmul passes against dense's 3 — and XLA
overlaps dense's logits HBM traffic with those matmuls, so the traffic is
only the binding cost when it EXCEEDS the matmul time.  Measured on v5e
(BENCH_FUSED_CE.json): at GPT-2-small's D=768 dense wins outright
(fused 0.48x); at D=128/V=64k the fusion wins 1.81x against dense-fp32
(exact softmax, traffic-bound) and 1.39x even against dense-bf16; and
when the logits tensor cannot materialize at all (64k tokens x 128k
vocab) the fusion is the only path that runs.  The cost model keeps a
conservative bf16 boundary (~D<120) — the D=128/bf16 row shows a
measured win just past it, deliberately left on dense by `auto`.
`fused_ce_wins` is this model made executable; models/gpt2.py's
loss_impl="auto" flips on it.  `bwd_impl="xla"` gives a middle point
(fused forward, one XLA recompute + materialized dlogits in the
backward).  All paths are equivalence-tested.

Ref: the reference has no analogue (torch materializes logits and calls
cross_entropy); this is a TPU-roofline-driven design, same family as
Liger's fused CE on GPU but built on the pallas grid/online-reduction
model instead of atomics.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401 — VMEM scratch


def _pick_block(n: int, candidates=(1024, 512, 256, 128, 64, 32, 16, 8)) -> int:
    for c in candidates:
        if n % c == 0 and c <= n:
            return c
    return n


# ----------------------------------------------------------------- forward
def _fwd_kernel(x_ref, w_ref, t_ref, lse_ref, tgt_ref, m_scr, s_scr, g_scr,
                *, bv: int, n_vb: int):
    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, m_scr.dtype)
        s_scr[...] = jnp.zeros(s_scr.shape, s_scr.dtype)
        g_scr[...] = jnp.zeros(g_scr.shape, g_scr.dtype)

    logits = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (bn, bv)
    m_prev = m_scr[...]                              # (bn, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    s_scr[...] = s_scr[...] * jnp.exp(m_prev - m_new) \
        + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True)
    m_scr[...] = m_new
    v_ids = vb * bv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    g_scr[...] += jnp.sum(
        jnp.where(v_ids == t_ref[...], logits, 0.0), axis=1, keepdims=True)

    @pl.when(vb == n_vb - 1)
    def _done():
        lse_ref[...] = m_scr[...] + jnp.log(s_scr[...])
        tgt_ref[...] = g_scr[...]


def _fwd_pallas(x2, w, t2, bn: int, bv: int, interpret: bool):
    n, d = x2.shape
    v = w.shape[0]
    n_rb, n_vb = n // bn, v // bv
    kernel = functools.partial(_fwd_kernel, bv=bv, n_vb=n_vb)
    lse, tgt = pl.pallas_call(
        kernel,
        grid=(n_rb, n_vb),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, w, t2)
    return lse, tgt


# ---------------------------------------------------------------- backward
def _dx_kernel(x_ref, w_ref, t_ref, lse_ref, dx_ref, *, bv: int):
    vb = pl.program_id(1)
    logits = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse_ref[...])
    v_ids = vb * bv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    p = p - (v_ids == t_ref[...]).astype(jnp.float32)

    @pl.when(vb == 0)
    def _init():
        dx_ref[...] = jnp.zeros(dx_ref.shape, dx_ref.dtype)

    dx_ref[...] += jax.lax.dot_general(
        p.astype(w_ref.dtype), w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _dw_kernel(x_ref, w_ref, t_ref, lse_ref, dw_ref, *, bv: int):
    rb = pl.program_id(1)
    logits = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse_ref[...])
    vb = pl.program_id(0)
    v_ids = vb * bv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    p = p - (v_ids == t_ref[...]).astype(jnp.float32)

    @pl.when(rb == 0)
    def _init():
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    dw_ref[...] += jax.lax.dot_general(
        p.astype(x_ref.dtype), x_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_pallas(x2, w, t2, lse, bn: int, bv: int, interpret: bool):
    n, d = x2.shape
    v = w.shape[0]
    n_rb, n_vb = n // bn, v // bv
    dx = pl.pallas_call(
        functools.partial(_dx_kernel, bv=bv),
        grid=(n_rb, n_vb),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(x2, w, t2, lse)
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, bv=bv),
        grid=(n_vb, n_rb),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bv, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bv, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((v, d), jnp.float32),
        interpret=interpret,
    )(x2, w, t2, lse)
    return dx, dw


# ------------------------------------------------------------- public entry
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ce(x2, w, t2, block_rows: int, bwd_impl: str):
    loss, _ = _fused_ce_fwd(x2, w, t2, block_rows, bwd_impl)
    return loss


def _blocks(x2, w, block_rows: int) -> Tuple[int, int]:
    # Largest legal (bn, bv) under a ~6 MiB working-set budget: the fp32
    # logits tile (bn*bv) plus the x/w tiles ((bn+bv)*d).  (1024, 1024)
    # measured fastest on v5e at d<=256; at d=512 that pair overflows VMEM
    # at compile (r5 sweep) and the budget steps bv down to 512.
    n, d = x2.shape
    v = w.shape[0]
    budget = 6 << 20
    for bn in (block_rows, 1024, 512, 256, 128, 64, 32, 16, 8):
        if bn > n or n % bn:
            continue
        for bv in (1024, 512, 256, 128, 64, 32, 16, 8):
            if bv > v or v % bv:
                continue
            if bn * bv * 4 + (bn + bv) * d * 4 <= budget:
                return bn, bv
    return (_pick_block(n, (128, 64, 32, 16, 8)),
            _pick_block(v, (128, 64, 32, 16, 8)))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fused_ce_fwd(x2, w, t2, block_rows: int, bwd_impl: str):
    bn, bv = _blocks(x2, w, block_rows)
    lse, tgt = _fwd_pallas(x2, w, t2, bn, bv, _interpret())
    loss = jnp.mean(lse - tgt)
    return loss, (x2, w, t2, lse)


def _fused_ce_bwd(block_rows: int, bwd_impl: str, res, g):
    x2, w, t2, lse = res
    n = x2.shape[0]
    scale = (g / n).astype(jnp.float32)
    if bwd_impl == "pallas":
        bn, bv = _blocks(x2, w, block_rows)
        dx, dw = _bwd_pallas(x2, w, t2, lse, bn, bv, _interpret())
        dx = dx * scale
        dw = dw * scale
    else:  # "xla": one recompute, dlogits materializes (but fwd logits never did)
        logits = jax.lax.dot_general(
            x2, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        p = jnp.exp(logits - lse)
        onehot = jax.nn.one_hot(t2[:, 0], w.shape[0], dtype=jnp.float32)
        dlogits = ((p - onehot) * scale).astype(x2.dtype)
        dx = jax.lax.dot_general(
            dlogits, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dw = jax.lax.dot_general(
            dlogits, x2, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return dx.astype(x2.dtype), dw.astype(w.dtype), None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_ce_wins(d_model: int, logits_dtype_bytes: int, peaks,
                  matmul_eff: float = 0.5) -> bool:
    """Roofline cost model, overlap-aware (measured r5, BENCH_FUSED_CE),
    against the ``peaks`` of the device the step runs on
    (``_private.accelerators.device_peaks(device_kind)``):
    XLA overlaps the dense path's logits traffic with its matmuls, so per
    (token, vocab) element dense costs max(3 matmul passes, ~5
    bytes-per-logit of HBM) while fused costs 5 matmul passes (fwd + 2x
    bwd recompute + dx/dW) with zero logits traffic.  Fused therefore
    wins only when dense is TRAFFIC-bound and D is small enough:
    ~D<120 for bf16 logits, ~D<240 for fp32 — i.e. the exact-softmax
    (fp32) regime on small heads (measured 1.81x at D=128/V=64k), plus
    the absolute win when logits cannot materialize at all.
    GPT-2-small's D=768 correctly stays dense.  `auto` loss dispatch
    (models/gpt2.py loss_fn) flips on this."""
    per_elem = 2.0 * d_model / (matmul_eff * peaks.flops)  # one matmul pass
    dense_s = max(3.0 * per_elem, 5.0 * logits_dtype_bytes / peaks.hbm_bw)
    fused_s = 5.0 * per_elem
    return fused_s < dense_s


def fused_lm_head_ce(x, wte, targets, block_rows: int = 1024,
                     bwd_impl: str = "pallas"):
    """Mean token cross-entropy of a tied LM head, logits never in HBM.

    x: (B, S, D) hidden states (any float dtype; matmuls run in x.dtype on
    the MXU with fp32 accumulation); wte: (V, D); targets: (B, S) int32.
    bwd_impl: "pallas" = fully fused backward (2x logits recompute, zero
    HBM logits); "xla" = single XLA recompute with materialized dlogits.
    """
    if bwd_impl not in ("pallas", "xla"):
        raise ValueError(f"bwd_impl must be pallas|xla, got {bwd_impl!r}")
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    t2 = targets.reshape(b * s, 1).astype(jnp.int32)
    return _fused_ce(x2, wte.astype(x.dtype), t2, block_rows, bwd_impl)
